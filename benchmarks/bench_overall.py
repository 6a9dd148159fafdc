"""Paper Fig. 10: latency / recall / memory for every method × θ × dataset.

The headline table: NAIVE (NLJ), INDEX, ES, ES+HWS (≈SIMJOIN), ES+SWS,
ES+MI, ES+MI+ADAPT. Memory = peak work-sharing cache entries (the paper's
online-memory metric; the index itself is offline, Fig. 13). Each row
carries the compressed-storage mode (``quant``) plus the distance-kernel
bytes moved per emitted pair, so an f32-vs-int8 sweep is
``run(quant_modes=("off", "sq8"))``.

``run_overlap`` is the wave-pipeline breakdown: the MI-join methods run
once with the double-buffered traversal⇆assembly overlap and once with
the sequential reference path, asserting the pair sets are identical and
reporting wall-clock plus the band-compacted re-rank's f32 gather bytes
per pair. ``run_early_exit`` is the PDX analogue: exit-on vs exit-off
wall-clock under ``pdx8`` on the clustered high-dim dataset, asserting
identical pair sets and reporting ``dims_scanned_frac``.
``run_trace_overhead`` is the tracing guard: the same cell min-of-N
timed with the profiler off vs on, asserting identical pair sets and
counters and that the profiler costs < 5% wall-clock (plus a small
additive slack for sub-second CI cells). ``run_planner`` is the JoinPlanner parity table:
hand-tuned knobs vs ``plan_config``'s choice per dataset, asserting
admissibility (identical pair sets at a matching operating point;
soundness + no recall loss when calibration steers to a different one)
and zero cap-overflow retries at predicted caps (the ``--planner-only``
CI leg). ``run_sharded`` is the N-device
mesh sweep: per-shard-count wall-clock and per-transfer-class /
per-collective byte meters in forced-host-device subprocesses, asserting
host bytes per wave stay independent of N_y. ``--json PATH`` writes all
tables as a JSON artifact (``BENCH_overall.json``) — CI runs the
``--overlap-only`` form as a smoke step and the ``--sharded-only`` form
on the forced-8-device leg, and snapshots are committed at the repo root
so the perf trajectory survives between PRs alongside
``BENCH_offline.json``.
"""
from __future__ import annotations

import argparse
import json
import time

from benchmarks.common import (REGIMES, SCALES, dist_bytes, emit,
                               run_method, theta_grid)

METHODS = ("nlj", "index", "es", "es_hws", "es_sws", "es_mi", "es_mi_adapt")


def run(scale: str = "ci", *, regimes=REGIMES, theta_idxs=(1, 3, 5, 7),
        methods=METHODS, quant_modes=("off",)) -> list[dict]:
    dim = SCALES[scale]["dim"]
    rows = []
    for regime in regimes:
        grid = theta_grid(regime, scale)
        for ti in theta_idxs:
            theta = grid[ti - 1]
            for method in methods:
                for quant in quant_modes:
                    res, dt, rec = run_method(regime, method, theta,
                                              scale=scale, quant=quant)
                    nbytes = dist_bytes(res, dim, quant)
                    rows.append(dict(
                        dataset=regime, theta_idx=ti, theta=theta,
                        method=method, quant=quant, seconds=dt, recall=rec,
                        pairs=len(res.pairs), n_dist=res.stats.n_dist,
                        n_rerank=res.stats.n_rerank,
                        bytes_per_pair=nbytes / max(len(res.pairs), 1),
                        cache_entries=res.stats.peak_cache_entries,
                        overflow=res.stats.n_overflow,
                        n_ood=res.stats.n_ood))
    return rows


def run_overlap(scale: str = "ci", *, regime: str = "manifold",
                theta_idx: int = 2,
                methods=("es_mi", "es_mi_adapt"),
                quant: str = "sq8") -> list[dict]:
    """MI-join wave-pipeline breakdown: overlap-on vs overlap-off
    wall-clock on identical configs, plus re-rank gather traffic.

    Each method cell runs both paths against the same cached indexes and
    asserts the emitted pair sets match bit-for-bit (``pairs_match``) —
    the pipeline is a pure scheduling change. ``rerank_bytes_per_pair``
    is the f32 traffic the band-compacted gather dispatched
    (``n_rerank_gather`` rows × d × 4B) amortized over emitted pairs:
    with compaction it tracks band occupancy, not pool capacity.
    """
    dim = SCALES[scale]["dim"]
    theta = theta_grid(regime, scale)[theta_idx - 1]
    rows = []
    for method in methods:
        cells = {}
        for overlap in (True, False):
            res, dt, rec = run_method(regime, method, theta, scale=scale,
                                      quant=quant, overlap=overlap)
            cells[overlap] = (res, dt, rec)
        res_on, dt_on, rec_on = cells[True]
        res_off, dt_off, _ = cells[False]
        match = res_on.pair_set() == res_off.pair_set()
        npairs = max(len(res_on.pairs), 1)
        rows.append(dict(
            dataset=regime, theta_idx=theta_idx, theta=theta,
            method=method, quant=quant,
            overlap_on_s=dt_on, overlap_off_s=dt_off,
            speedup=dt_off / max(dt_on, 1e-9),
            pairs=len(res_on.pairs), pairs_match=match,
            recall=rec_on, n_rerank=res_on.stats.n_rerank,
            rerank_gather=res_on.stats.n_rerank_gather,
            rerank_bytes_per_pair=(res_on.stats.n_rerank_gather * dim * 4
                                   / npairs),
            wait_s=res_on.stats.wait_seconds))
    return rows


def run_trace_overhead(scale: str = "ci", *, regime: str = "manifold",
                       theta_idx: int = 2, method: str = "es_mi",
                       quant: str = "sq8", repeats: int = 3,
                       slack_s: float = 0.15) -> list[dict]:
    """Tracing overhead guard: one pipelined MI-join cell timed without
    and under a ``jax.profiler`` session (``obs.trace.profile``: the
    program spans and the device ops), min-of-``repeats`` per arm.

    Asserts (a) the emitted pair sets and the work counters are
    identical — tracing is observation, never scheduling — and (b) the
    traced arm's best
    wall-clock stays within 5% of the untraced best plus ``slack_s``
    seconds of additive slack (CI cells are sub-second, where a fixed 5%
    would be dominated by scheduler noise; the relative bound is what
    matters at paper scale).
    """
    import contextlib
    import tempfile

    from repro.obs import trace as obs_trace
    theta = theta_grid(regime, scale)[theta_idx - 1]

    def arm(traced: bool):
        times, res = [], None
        for _ in range(repeats):
            with tempfile.TemporaryDirectory() as log_dir:
                with (obs_trace.profile(log_dir) if traced
                      else contextlib.nullcontext()):
                    res, dt, _ = run_method(regime, method, theta,
                                            scale=scale, quant=quant)
            times.append(dt)
        return res, min(times)

    res_off, t_off = arm(False)
    res_on, t_on = arm(True)
    match = res_on.pair_set() == res_off.pair_set()
    assert match, (method, quant,
                   len(res_on.pair_set() ^ res_off.pair_set()))
    for k in ("n_dist", "n_rerank", "n_slots", "n_lane_iters", "n_iters"):
        assert getattr(res_on.stats, k) == getattr(res_off.stats, k), k
    budget = 1.05 * t_off + slack_s
    assert t_on <= budget, (
        f"tracing overhead over budget: traced {t_on:.3f}s vs "
        f"untraced {t_off:.3f}s (budget {budget:.3f}s)")
    return [dict(
        dataset=regime, theta_idx=theta_idx, theta=theta,
        method=method, quant=quant,
        trace_off_s=t_off, trace_on_s=t_on,
        overhead_frac=(t_on - t_off) / max(t_off, 1e-9),
        pairs=len(res_on.pairs), pairs_match=match)]


def run_early_exit(scale: str = "ci_hd", *, regime: str = "clustered",
                   theta_idx: int = 2,
                   methods=("nlj", "es_mi"),
                   quant: str = "pdx8") -> list[dict]:
    """PDX early-exit breakdown: exit-on vs exit-off (full slab scans)
    wall-clock on identical configs, on the clustered high-dim dataset
    where lanes actually retire early.

    Each method cell runs both paths and *asserts* the emitted pair sets
    match bit-for-bit (``pairs_match`` — the tail bound is certified, so
    exit is a pure wall-clock change); ``dims_scanned_frac`` is the
    fraction of candidate dimensions the slab kernels read with exit on
    (< 1.0 is the tier earning its keep; off reports exactly 1.0).
    """
    from repro.core.types import TraversalConfig
    dim = SCALES[scale]["dim"]
    theta = theta_grid(regime, scale)[theta_idx - 1]
    rows = []
    for method in methods:
        cells = {}
        for ee in (True, False):
            res, dt, rec = run_method(regime, method, theta, scale=scale,
                                      quant=quant,
                                      tcfg=TraversalConfig(early_exit=ee))
            cells[ee] = (res, dt, rec)
        res_on, dt_on, rec_on = cells[True]
        res_off, dt_off, _ = cells[False]
        match = res_on.pair_set() == res_off.pair_set()
        assert match, (method, quant,
                       len(res_on.pair_set() ^ res_off.pair_set()))
        rows.append(dict(
            dataset=regime, dim=dim, theta_idx=theta_idx, theta=theta,
            method=method, quant=quant,
            exit_on_s=dt_on, exit_off_s=dt_off,
            speedup=dt_off / max(dt_on, 1e-9),
            pairs=len(res_on.pairs), pairs_match=match,
            recall=rec_on,
            dims_scanned_frac=res_on.stats.dims_scanned_frac,
            dims_scanned_frac_off=res_off.stats.dims_scanned_frac,
            bytes_per_pair=(dist_bytes(res_on, dim, quant)
                            / max(len(res_on.pairs), 1))))
    return rows


def run_sharded(scale: str = "ci", *, regime: str = "manifold",
                theta_idx: int = 2, shard_counts=(1, 2, 4, 8),
                method: str = "es_mi", quant: str = "sq8",
                wave: int = 128) -> list[dict]:
    """N-device mesh driver vs single-device: wall-clock, per-transfer-
    class bytes (feedback / band / assembly), per-collective bytes
    (all_gather / ppermute / psum), and ``shard_band_imbalance``
    (max/mean ambiguous-band occupancy across shards).

    Each shard count runs in a subprocess with that many forced host
    devices (jax locks the device count at first init). The children run
    on the CPU (``JAX_PLATFORMS=cpu``, so none of them reaches for a chip
    this process may hold), and every row says so in ``platform``. Two
    extra ``nlj``
    cells run the same shard count and θ at N_y and 4·N_y and *assert*
    host bytes per wave stay sub-linear in N_y (< 2× for 4× rows): the
    on-device pool merge ships only the band-compacted merged pool
    (S × B × merge_cap int32), so host traffic tracks band occupancy,
    not the data-side row count.
    """
    import os
    import subprocess
    import sys

    from repro.data.vectors import make_dataset, thresholds

    n_data = 8_000 if scale == "ci" else 60_000
    n_query, dim = (256, 48) if scale == "ci" else (1_000, 96)
    ref = make_dataset(regime, n_data=n_data, n_query=n_query, dim=dim,
                       seed=5)
    theta = float(thresholds(ref, 7)[theta_idx - 1])

    def cell(n_shards, *, n_data=n_data, method=method, quant=quant):
        env = dict(os.environ, REPRO_BENCH_DEVICES=str(max(n_shards, 1)),
                   PYTHONPATH="src", JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks._sharded_worker",
             "--n-data", str(n_data), "--n-query", str(n_query),
             "--dim", str(dim), "--shards", str(n_shards),
             "--method", method, "--quant", quant,
             "--theta", repr(theta), "--wave", str(wave)],
            capture_output=True, text=True, env=env, check=True)
        return json.loads(out.stdout.strip().splitlines()[-1])

    rows = [cell(s) for s in shard_counts]
    base_s = rows[0]["seconds"]
    for r in rows:
        r["speedup_vs_1"] = base_s / max(r["seconds"], 1e-9)

    # host-bytes-per-wave independence of N_y: same shards, same θ, the
    # pure mesh NLJ driver (host traffic == the merged pool transfer)
    s_chk = max(s for s in shard_counts if s > 1)
    small = cell(s_chk, n_data=n_data // 2, method="nlj", quant="off")
    big = cell(s_chk, n_data=2 * n_data, method="nlj", quant="off")
    ratio = big["host_bytes_per_wave"] / max(small["host_bytes_per_wave"],
                                             1e-9)
    assert ratio < 2.0, (
        f"host bytes per wave grew {ratio:.2f}x for 4x N_y "
        f"({small['host_bytes_per_wave']:.0f} -> "
        f"{big['host_bytes_per_wave']:.0f}B): the pool merge is leaking "
        f"N_y-proportional traffic to the host")
    for r in (small, big):
        r["speedup_vs_1"] = float("nan")
        r["ny_check"] = True
        r["host_bytes_ratio"] = ratio
    return rows + [small, big]


def run_planner(scale: str = "ci", *, regimes=REGIMES, theta_idx: int = 2,
                method: str = "es_mi", quant: str = "sketch8",
                wave: int = 128) -> list[dict]:
    """JoinPlanner parity table: hand-tuned knobs vs the planner's
    choice, per dataset.

    The hand arm runs the fixed (method, quant, wave) cell through
    ``run_method`` (which also calibrates the persistent engine's cost
    table); the planned arm asks ``JoinEngine.plan_config`` for the
    operating point — with calibrated candidates, the planner picks by
    measured cost — warms that exact config, then times it.

    Admissibility is asserted per what the planner was free to change:
    when it lands on the hand arm's (method, quant), the pair sets must
    be bit-identical (knobs like wave size and cap seeds are advisory —
    they move wall-clock, never pairs); when it picks a *different*
    operating point (e.g. exact NLJ once calibration shows it cheaper
    than an approximate traversal), set identity is the wrong bar —
    instead the planned arm must be sound (⊆ exact truth) and lose no
    recall vs the hand arm. Both arms must take **zero** cap-overflow
    retries at the predicted caps (``JoinStats.overflow_retries``).
    """
    from benchmarks.common import dataset, engine, truth
    from repro.core.types import JoinConfig, recall as _recall

    rows = []
    for regime in regimes:
        theta = theta_grid(regime, scale)[theta_idx - 1]
        res_h, dt_h, rec_h = run_method(regime, method, theta,
                                        scale=scale, quant=quant,
                                        wave=wave)
        ds = dataset(regime, scale)
        eng = engine(regime, scale)
        cfg_p = eng.plan_config(
            ds.X, JoinConfig(method=method, theta=theta, quant=quant,
                             wave_size=wave))
        # warm the planned cell so its timing is compile-free like the
        # hand arm's (run_method warms + re-calibrates as a side effect)
        run_method(regime, cfg_p.method, theta, scale=scale,
                   quant=cfg_p.quant, wave=cfg_p.wave_size)
        t0 = time.perf_counter()
        res_p = eng.join(ds.X, cfg_p)
        dt_p = time.perf_counter() - t0
        same_point = (cfg_p.method, cfg_p.quant) == (method, quant)
        match = res_p.pair_set() == res_h.pair_set()
        rec_p = _recall(res_p, truth(regime, theta, scale))
        tset = set(map(tuple, truth(regime, theta, scale).tolist()))
        sound = not (res_p.pair_set() - tset)
        admissible = (match if same_point
                      else (sound and rec_p >= rec_h - 1e-9))
        assert admissible, (
            f"{regime}: planned ({cfg_p.method}/{cfg_p.quant}) vs "
            f"hand-tuned ({method}/{quant}): "
            + (f"pair sets differ by "
               f"{len(res_p.pair_set() ^ res_h.pair_set())}"
               if same_point else
               f"sound={sound} recall {rec_p:.4f} < {rec_h:.4f}"))
        assert res_p.stats.overflow_retries == 0, (
            f"{regime}: planned run took "
            f"{res_p.stats.overflow_retries} cap-overflow retries at "
            f"predicted caps")
        plan = eng.planner.plan(
            ds.X, theta=theta,
            pool_cap=int(cfg_p.traversal.pool_cap),
            n_shards=eng.n_shards, dim=int(ds.Y.shape[1]))
        rows.append(dict(
            dataset=regime, theta_idx=theta_idx, theta=theta,
            hand_method=method, hand_quant=quant, hand_wave=wave,
            planned_method=cfg_p.method, planned_quant=cfg_p.quant,
            planned_wave=cfg_p.wave_size, plan_source=plan.source,
            hand_s=dt_h, planned_s=dt_p,
            speedup=dt_h / max(dt_p, 1e-9),
            pairs=len(res_p.pairs), same_point=same_point,
            pairs_match=match, admissible=admissible,
            recall=rec_h, planned_recall=rec_p,
            predicted_pairs=plan.predicted_join_size,
            hand_retries=res_h.stats.overflow_retries,
            planned_retries=res_p.stats.overflow_retries))
    return rows


def run_serve(scale: str = "ci", *, regimes=("manifold", "clustered"),
              theta_idx: int = 2, n_requests: int = 16,
              quant_modes=("off", "sq8"), method: str = "es_sws",
              buckets=(64, 128), seed: int = 0) -> list[dict]:
    """JoinService admission-path benchmark: one multi-tenant shuffled
    request stream through the continuous-batching front end.

    Reports admission latency (mean / max over the stream), serving
    throughput (queries/s after warmup), wave-lane occupancy, and the
    XLA compile-counter delta across the serving phase — asserted flat,
    the bucket-ladder invariant the front end exists to provide.
    """
    import numpy as np

    from benchmarks.common import dataset
    from repro.obs import metrics as obs_metrics
    from repro.serve import JoinRequest, JoinService, ServiceConfig

    dim = SCALES[scale]["dim"]
    svc = JoinService(ServiceConfig(buckets=tuple(buckets),
                                    max_queue=4 * n_requests))
    tenants = {}
    for i, regime in enumerate(regimes):
        ds = dataset(regime, scale)
        theta = theta_grid(regime, scale)[theta_idx - 1]
        svc.load(regime, ds.Y)
        tenants[regime] = (ds, theta)
    t0 = time.perf_counter()
    for regime, (ds, theta) in tenants.items():
        svc.warmup(regime, thetas=[theta], methods=(method,),
                   quants=quant_modes)
    warm_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    names = list(tenants)
    for uid in range(n_requests):
        regime = names[int(rng.integers(len(names)))]
        ds, theta = tenants[regime]
        n_max = int(ds.X.shape[0])
        n = int(rng.integers(1, min(2 * max(buckets), n_max) + 1))
        lo = int(rng.integers(0, n_max - n + 1))
        svc.submit(JoinRequest(
            uid=uid, tenant=regime,
            X=np.asarray(ds.X, np.float32)[lo:lo + n], theta=theta,
            method=method, quant=quant_modes[uid % len(quant_modes)]))
    c0 = obs_metrics.compile_count()
    t0 = time.perf_counter()
    done = svc.run()
    dt = time.perf_counter() - t0
    compiles = obs_metrics.compile_count() - c0
    assert compiles == 0, (
        f"{compiles} recompiles in steady-state serving (bucket ladder "
        f"not warm)")
    served = [sj for sj in done.values() if sj.ok]
    n_queries = sum(sj.n_queries for sj in served)
    h = svc.metrics.get("serve_join.admission_seconds")
    occ = svc.metrics.get("serve_join.occupancy")
    return [dict(
        scale=scale, method=method, tenants=len(tenants),
        requests=len(served), queries=n_queries,
        pairs=sum(len(sj.pairs) for sj in served),
        warmup_s=warm_s, serve_s=dt,
        queries_per_s=n_queries / max(dt, 1e-9),
        admission_mean_s=h.sum / max(h.count, 1),
        occupancy_mean=occ.sum / max(occ.count, 1),
        serve_compiles=compiles)]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", default="ci")
    ap.add_argument("--regimes", nargs="*", default=list(REGIMES))
    ap.add_argument("--overlap-only", action="store_true",
                    help="run only the wave-pipeline and early-exit "
                         "breakdowns (the CI smoke configuration)")
    ap.add_argument("--sharded-only", action="store_true",
                    help="run only the N-device mesh sweep (the CI "
                         "forced-8-device leg)")
    ap.add_argument("--planner-only", action="store_true",
                    help="run only the JoinPlanner parity table "
                         "(planned vs hand-tuned knobs; the CI planner "
                         "leg — asserts identical pairs and zero "
                         "overflow retries at predicted caps)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows + metadata as a JSON artifact "
                         "(e.g. BENCH_overall.json for the CI upload)")
    args = ap.parse_args(argv)
    if args.sharded_only:
        sharded_rows = run_sharded(args.scale, regime=args.regimes[0])
        emit(sharded_rows)
        if args.json:
            payload = dict(bench="overall", scale=args.scale,
                           sharded=sharded_rows)
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"# wrote {args.json}")
        return
    if args.planner_only:
        planner_rows = run_planner(args.scale,
                                   regimes=tuple(args.regimes))
        emit(planner_rows)
        if args.json:
            payload = dict(bench="overall", scale=args.scale,
                           planner=planner_rows)
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"# wrote {args.json}")
        return
    rows = ([] if args.overlap_only
            else run(args.scale, regimes=tuple(args.regimes)))
    overlap_rows = run_overlap(args.scale, regime=args.regimes[0])
    early_exit_rows = run_early_exit(
        "full_hd" if args.scale == "full" else "ci_hd")
    trace_rows = run_trace_overhead(args.scale, regime=args.regimes[0])
    serve_rows = run_serve(args.scale)
    planner_rows = ([] if args.overlap_only
                    else run_planner(args.scale,
                                     regimes=tuple(args.regimes)))
    sharded_rows = ([] if args.overlap_only
                    else run_sharded(args.scale, regime=args.regimes[0]))
    emit(rows)
    emit(overlap_rows)
    emit(early_exit_rows)
    emit(trace_rows)
    emit(serve_rows)
    emit(planner_rows)
    emit(sharded_rows)
    if args.json:
        payload = dict(bench="overall", scale=args.scale, rows=rows,
                       overlap=overlap_rows, early_exit=early_exit_rows,
                       trace_overhead=trace_rows, serve=serve_rows,
                       planner=planner_rows, sharded=sharded_rows)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
