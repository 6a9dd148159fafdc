"""Vector-join launcher — the paper's operator as a first-class command.

Runs any §5.1.2 method on a synthetic Table-1-regime dataset (or .npy
inputs) through a persistent ``JoinEngine``, reporting latency / recall /
distance computations. ``--shards N`` shards the data side over N local
devices; ``--stream B`` feeds queries as streaming batches of B through
``engine.submit`` (carrying the work-sharing cache between batches);
``--sweep`` reruns every Table-2 threshold against the same cached index.

  PYTHONPATH=src python -m repro.launch.join --method es_mi_adapt \\
      --regime ood --n-data 20000 --n-query 500 --theta-q 2
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import numpy as np

from repro.configs.vectorjoin import ENGINE_PRESETS, make_engine, preset
from repro.core import exact_join_pairs
from repro.core.types import METHODS, QUANT_MODES
from repro.data.vectors import make_dataset, thresholds
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import trace as obs_trace


def shards_arg(v: str) -> int:
    """``--shards`` parser: ``auto`` = one shard per local device (0 is
    the engine's auto sentinel), otherwise a positive int."""
    if v.strip().lower() == "auto":
        return 0
    return int(v)


def check_shards(ap: argparse.ArgumentParser, n_shards: int) -> None:
    """Fail at the launcher with a clear message when more shards are
    requested than JAX devices exist, instead of erroring inside
    ``shard_map`` mesh construction."""
    import jax

    nd = len(jax.devices())
    if n_shards > nd:
        ap.error(
            f"--shards {n_shards}: only {nd} JAX device(s) visible; use "
            f"--shards auto, or force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards} "
            f"on CPU")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", choices=METHODS, default="es_mi_adapt")
    ap.add_argument("--regime", default="manifold",
                    choices=("manifold", "weak", "clustered", "ood"))
    ap.add_argument("--n-data", type=int, default=20_000)
    ap.add_argument("--n-query", type=int, default=1_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--theta", type=float)
    ap.add_argument("--theta-q", type=int, default=1,
                    help="1-based index into the 7 Table-2-style thresholds")
    ap.add_argument("--wave", type=int, default=256)
    ap.add_argument("--quant", choices=QUANT_MODES,
                    default=None,
                    help="compressed storage: the FilterCascade tier "
                         "chain joins filter through — sq8 traverses "
                         "int8 codes and re-ranks survivors with exact "
                         "f32; sketch8 adds a 1-bit Hamming-sketch prune "
                         "tier above int8; pdx8 swaps int8 for the "
                         "dimension-partitioned PdxTier whose kernels "
                         "early-exit mid-vector on certified tail "
                         "bounds; sketchpdx8 stacks the sketch above it "
                         "(default: the engine spec's quant mode)")
    ap.add_argument("--early-exit", choices=("on", "off"), default="on",
                    help="PDX modes: retire candidate lanes mid-vector "
                         "once partial distance + certified tail bound "
                         "exceeds θ². Certified ⇒ the emitted pair set "
                         "is identical on/off; off is the full-scan "
                         "wall-clock baseline (the REPRO_EARLY_EXIT env "
                         "var overrides both)")
    ap.add_argument("--quant-build", choices=("off", "sq8", "sketch8"),
                    default=None,
                    help="drive the offline index builds through the "
                         "cascade too: certified bounds resolve the kNN "
                         "sweep and RNG prune, f32 only for the ambiguous "
                         "band — identical edges, less f32 traffic "
                         "(default: the engine spec's quant_build mode)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the double-buffered wave pipeline and "
                         "run the strictly sequential reference path "
                         "(bisection escape hatch; pair sets are "
                         "identical either way — the REPRO_OVERLAP env "
                         "var overrides both)")
    ap.add_argument("--plan", choices=("manual", "auto"), default="manual",
                    help="auto: let the engine's JoinPlanner pick the "
                         "operating point (method, quant, wave bucket, "
                         "cap seeds) from its LSH selectivity estimate "
                         "and calibrated cost table — --method/--quant "
                         "become defaults, not pins. Advisory-only: the "
                         "emitted pair set is identical to manual knobs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine-spec", default="default",
                    help="EngineSpec preset "
                         "(default|ci|serving|serving_sq8|serving_sketch8)")
    ap.add_argument("--shards", type=shards_arg, default=1,
                    help="shard the data side over N local devices (MI "
                         "and nlj methods); 'auto' (or 0) = one shard "
                         "per device. The MeshPlan may re-split shards "
                         "over a second dimension axis for nlj (hybrid "
                         "dimension+vector partitioning)")
    ap.add_argument("--stream", type=int, default=0, metavar="B",
                    help="submit queries as streaming batches of B")
    ap.add_argument("--sweep", action="store_true",
                    help="rerun all 7 thresholds on the cached index")
    ap.add_argument("--distributed", action="store_true",
                    help="alias for --shards 0 (all local devices)")
    ap.add_argument("--no-truth", action="store_true",
                    help="skip the exact NLJ ground truth (big inputs)")
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="run the joins under a jax.profiler session "
                         "that writes to DIR: the program's join/wave "
                         "spans and the device ops on one clock (an "
                         ".xplane.pb for TensorBoard/XProf and a "
                         ".trace.json.gz to load at ui.perfetto.dev)")
    ap.add_argument("--metrics-dump", action="store_true",
                    help="print the engine's metrics registry in "
                         "Prometheus exposition format after the run "
                         "(cache hit/miss/eviction/tombstone counters, "
                         "per-shard band gauges, wave histograms)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    ds = make_dataset(args.regime, n_data=args.n_data, n_query=args.n_query,
                      dim=args.dim, seed=args.seed)
    grid = [float(t) for t in thresholds(ds, 7)]
    theta = args.theta or grid[args.theta_q - 1]
    # --quant / --quant-build win; otherwise inherit the engine spec's
    # modes (so --engine-spec serving_sq8 actually serves compressed)
    quant = args.quant or ENGINE_PRESETS[args.engine_spec].quant
    quant_build = (args.quant_build
                   if args.quant_build is not None
                   else ENGINE_PRESETS[args.engine_spec].quant_build)
    cfg = preset(args.method, theta=theta)
    cfg = dataclasses.replace(
        cfg, wave_size=args.wave, quant=quant,
        overlap=not args.no_overlap,
        traversal=dataclasses.replace(
            cfg.traversal, early_exit=(args.early_exit != "off")))

    n_shards = 0 if args.distributed else args.shards
    check_shards(ap, n_shards)
    eng = make_engine(ds.Y, args.engine_spec, default=cfg,
                      n_shards=n_shards, quant_build=quant_build)
    if args.plan == "auto":
        # let the planner pick method/quant/wave from the LSH estimate
        # (cost-table calibration is empty on a cold launcher, so this
        # exercises the selectivity heuristic; caps stay overflow-
        # checked, so the pair set cannot change)
        cfg = eng.plan_config(ds.X, cfg)
        quant = cfg.quant
        # sticky-cache hit on the exact plan plan_config just made
        plan = eng.planner.plan(
            ds.X, theta=theta, pool_cap=int(cfg.traversal.pool_cap),
            n_shards=eng.n_shards, dim=args.dim)
        print(f"[join] plan auto: method={cfg.method} quant={cfg.quant} "
              f"wave={cfg.wave_size} rerank_cap={plan.rerank_cap} "
              f"merge_cap={plan.merge_cap} mesh={plan.mesh_kind} "
              f"predicted_pairs={plan.predicted_join_size:.0f} "
              f"source={plan.source}")
    method = cfg.method
    if (args.stream and eng.n_shards > 1
            and method not in ("nlj", "es_mi", "es_mi_adapt")):
        ap.error(f"--stream with --shards supports nlj/es_mi/"
                 f"es_mi_adapt, not {method}")

    print(f"[join] {args.regime} |X|={args.n_query} |Y|={args.n_data} "
          f"dim={args.dim} θ={theta:.4f} method={method} "
          f"shards={eng.n_shards} quant={quant} quant_build={quant_build} "
          f"overlap={'off' if args.no_overlap else 'on'}")

    prof = (obs_trace.profile(args.trace) if args.trace
            else contextlib.nullcontext())
    with prof:
        t0 = time.perf_counter()
        if args.stream:
            parts = [eng.submit(ds.X[b0:b0 + args.stream], cfg)
                     for b0 in range(0, args.n_query, args.stream)]
            pairs = np.concatenate([r.pairs for r in parts], axis=0)
            n_dist = sum(r.stats.n_dist for r in parts)
            dt = time.perf_counter() - t0
            print(f"[join] {len(parts)} streamed batches: {len(pairs)} pairs "
                  f"in {dt:.2f}s (n_dist={n_dist})")
        else:
            res = eng.join(ds.X, cfg)
            dt = time.perf_counter() - t0
            extra = (f", rerank={res.stats.n_rerank}, "
                     f"quant_bytes={res.stats.quant_bytes}"
                     if quant != "off" else "")
            if quant == "sketch8":
                pruned = res.stats.n_dist - res.stats.n_esc8
                extra += (f", esc8={res.stats.n_esc8}, sketch_pruned={pruned}"
                          f" ({pruned / max(res.stats.n_dist, 1):.0%})")
            if quant in ("pdx8", "sketchpdx8"):
                extra += f", dims_frac={res.stats.dims_scanned_frac:.3f}"
            print(f"[join] {len(res.pairs)} pairs in {dt:.2f}s "
                  f"(n_dist={res.stats.n_dist}, ood={res.stats.n_ood}, "
                  f"builds={eng.n_index_builds}{extra})")
            pairs = res.pairs

        if args.sweep:
            for i, th in enumerate(grid):
                t0 = time.perf_counter()
                r = eng.join(ds.X, cfg, theta=th)
                print(f"[sweep] θ{i + 1}={th:.4f}: {len(r.pairs)} pairs in "
                      f"{time.perf_counter() - t0:.2f}s "
                      f"(builds={eng.n_index_builds})")
    if args.trace:
        print(f"[join] wrote a profiler trace under {args.trace}")
    if args.metrics_dump:
        print(eng.metrics.prometheus_text(), end="")

    if not args.no_truth:
        truth = exact_join_pairs(ds.X, ds.Y, theta)
        got = set(map(tuple, pairs.tolist()))
        tset = set(map(tuple, truth.tolist()))
        rec = len(got & tset) / max(len(tset), 1)
        sound = not (got - tset)
        print(f"[join] recall={rec:.4f} sound={sound} truth={len(tset)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
