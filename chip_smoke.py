#!/usr/bin/env python3
"""On-chip smoke run of the vector join: the main path, once, at SIFT1M shape.

  python3 chip_smoke.py               # one TPU chip
  python3 chip_smoke.py --chips 4     # sharded path on a 2x2 host only

Deployment: the ``sift-like`` regime (``data.vectors.TABLE1_REGIMES``),
shaped like ANN-benchmarks ``sift-128-euclidean``: d=128, L2, 10,000
queries generated from ``--seed``, θ = θ₁ of the seven Table-2
thresholds. The data side is cut from SIFT1M's 1,000,000 rows to 500,000:
the quadratic index build (kNN sweep) takes about ten minutes a build at
1M rows on one v5e, and the run needs two builds (G_(X∪Y) for the
merged-index joins, G_Y for the service).

One chip, in one process, each phase failing loudly:

  1. device check — a TPU, or exit non-zero;
  2. ``make_engine`` (the ``launch/join.py`` path) and the merged-index
     build, timed;
  3. ``engine.join`` with es_mi_adapt under quant off and sq8, and the
     exact ``nlj``: pairs, compile seconds of the first call, wall seconds
     of a second, warm call;
  4. ``JoinService``: one tenant, warmup, then 8 requests of 64–256
     queries at the service's default method under off and sq8; the
     compile count must not move after warmup;
  5. a float64 numpy reference on the host (independent of the code under
     test) for the first 1,000 queries against all of Y: recall of
     every approximate run ≥ 0.9, ``nlj`` equal to it, and no emitted pair
     beyond θ — both up to the matmul rounding band at θ;
  6. peak device bytes, the implementation every ``kernels.ops`` entry
     took (no reference or interpret path), and ``tpu_custom_call`` in the
     compiled wave epilogue.

``--chips 4`` runs only the sharded path (``n_shards=4`` es_mi and nlj),
the single-device run and the same reference, and prints per-device
memory. The last line of stdout is the JSON result; nothing is printed
there unless every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_QUERY = 10_000      # SIFT1M's published query count
N_SAMPLE = 1_000      # queries checked against the float64 reference
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_seconds = [0.0]


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _on_compile(name: str, duration: float = 0.0, **kw) -> None:
    if name == _COMPILE_EVENT:
        _compile_seconds[0] += duration


def timed_twice(fn):
    """(result, compile seconds of the first call, warm wall seconds)."""
    c0 = _compile_seconds[0]
    fn()
    compile_s = _compile_seconds[0] - c0
    t0 = time.perf_counter()
    out = fn()
    return out, compile_s, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# float64 reference (host numpy; shares no code with the join)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Reference:
    """Exact pairs of the sample queries, and the ulp band at θ."""
    theta: float
    n_sample: int
    truth: set          # (q, y) with float64 distance < θ
    band: set           # (q, y) whose float64 d² is within the band of θ²
    guard: float        # relative band width (the repo's MATMUL_GUARD)


def reference(X: np.ndarray, Y: np.ndarray, theta: float, n_sample: int,
              guard: float, block: int = 65536) -> Reference:
    xs = X[:n_sample].astype(np.float64)
    xn = np.sum(xs * xs, axis=1)
    th2 = theta * theta
    truth, band = set(), set()
    for y0 in range(0, Y.shape[0], block):
        yb = Y[y0:y0 + block].astype(np.float64)
        yn = np.sum(yb * yb, axis=1)
        d2 = xn[:, None] + yn[None, :] - 2.0 * (xs @ yb.T)
        qi, yi = np.nonzero(d2 < th2)
        truth.update(zip(qi.tolist(), (yi + y0).tolist()))
        tol = guard * (xn[:, None] + yn[None, :])
        qi, yi = np.nonzero(np.abs(d2 - th2) <= tol)
        band.update(zip(qi.tolist(), (yi + y0).tolist()))
    return Reference(theta, n_sample, truth, band, guard)


def beyond_theta(pairs: np.ndarray, X: np.ndarray, Y: np.ndarray,
                 theta: float, guard: float, block: int = 1 << 18) -> int:
    """Emitted pairs whose float64 distance exceeds θ beyond the band."""
    bad = 0
    for p0 in range(0, len(pairs), block):
        q, y = pairs[p0:p0 + block, 0], pairs[p0:p0 + block, 1]
        xq, yy = X[q].astype(np.float64), Y[y].astype(np.float64)
        d2 = np.sum((xq - yy) ** 2, axis=1)
        tol = guard * (np.sum(xq * xq, axis=1) + np.sum(yy * yy, axis=1))
        bad += int(np.count_nonzero(d2 >= theta * theta + tol))
    return bad


def sample_pairs(pairs: np.ndarray, n_sample: int) -> set:
    sel = pairs[pairs[:, 0] < n_sample]
    return set(map(tuple, sel.tolist()))


def judge(tag: str, pairs: np.ndarray, ref: Reference, X, Y, *,
          exact: bool, q_lo: int = 0, q_hi: int | None = None) -> float:
    """Soundness of every pair; recall (or equality) on the sample."""
    bad = beyond_theta(pairs, X, Y, ref.theta, ref.guard)
    check(bad == 0, f"{tag}: {bad} pairs beyond θ outside the ulp band")
    q_hi = ref.n_sample if q_hi is None else q_hi
    got = {p for p in sample_pairs(pairs, ref.n_sample) if q_lo <= p[0] < q_hi}
    want = {p for p in ref.truth if q_lo <= p[0] < q_hi}
    rec = len(got & want) / max(len(want), 1)
    if exact:
        diff = got ^ want
        off_band = diff - ref.band
        say(f"{tag}: equal to the float64 reference on {len(want)} pairs, "
            f"{len(diff)} differing pairs all in the ulp band at θ "
            f"(off-band: {len(off_band)})")
        check(not off_band, f"{tag}: {len(off_band)} pairs differ from the "
                            f"reference outside the ulp band")
    else:
        say(f"{tag}: recall={rec:.4f} on {len(want)} reference pairs, "
            f"0 pairs beyond θ")
        check(rec >= 0.9, f"{tag}: recall {rec:.4f} < 0.9")
    return rec


# ---------------------------------------------------------------------------
# device helpers
# ---------------------------------------------------------------------------

def memory(dev) -> dict:
    st = dev.memory_stats() or {}
    return {"peak_bytes_in_use": int(st.get("peak_bytes_in_use", 0)),
            "bytes_in_use": int(st.get("bytes_in_use", 0))}


def check_impls() -> None:
    """Every kernels.ops entry that ran took the compiled Pallas kernel
    (``topk_merge``: XLA's ``top_k``, its one path on every backend) —
    never the ``ref`` oracle or interpret mode."""
    from repro.kernels import ops
    for entry, impls in sorted(ops.IMPLS_USED.items()):
        say(f"kernels.ops.{entry}: {sorted(impls)}")
        want = {"xla"} if entry == "topk_merge" else {"pallas"}
        check(impls <= want,
              f"{entry} ran {sorted(impls - want)} on the chip")


def wave_epilogue_hlo(eng, X, cfg) -> str:
    """Compiled HLO of the sq8 wave epilogue (band-compacted exact
    re-rank through the scalar-prefetch gather kernel)."""
    import jax
    import jax.numpy as jnp

    from repro.engine import waves
    from repro.quant.cascade import build_cascade

    merged = eng.merged_index(X)
    casc = build_cascade(merged.vecs, "sq8")
    B, C = cfg.wave_size, cfg.traversal.pool_cap
    xw = jnp.asarray(X[:B])

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)
    lowered = waves._finalize_wave.lower(
        casc, casc.encode(xw), merged.vecs, xw, s((B, C), jnp.int32),
        s((B, C), jnp.float32), s((B,), jnp.int32), s((B,), jnp.bool_),
        s((B,), jnp.int32), s((), jnp.float32), cap=cfg.traversal.rerank_cap,
        dist_impl=None, seed_mode="none", seeds_max=cfg.traversal.seeds_max,
        early_exit=True)
    return lowered.compile().as_text()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def make_data(args):
    from repro.data.vectors import table1_dataset, thresholds
    t0 = time.perf_counter()
    ds = table1_dataset("sift-like", n_data=args.n_data,
                        n_query=N_QUERY, seed=args.seed)
    theta = float(thresholds(ds, 7)[0])
    say(f"data: sift-like |Y|={ds.Y.shape[0]} |X|={ds.X.shape[0]} "
        f"d={ds.Y.shape[1]} θ1={theta!r} "
        f"({time.perf_counter() - t0:.1f}s on the host)")
    if args.n_data < 1_000_000:
        say(f"CUT: N_y = {args.n_data} (SIFT1M has 1,000,000 rows)")
    return ds, theta


def run_single(args, ds, theta, ref) -> None:
    import jax

    from repro.configs.vectorjoin import make_engine, preset
    from repro.obs import metrics as obs_metrics
    from repro.serve import JoinRequest, JoinService, ServiceConfig

    X, Y = ds.X, ds.Y
    cfg = preset("es_mi_adapt", theta=theta)
    eng = make_engine(Y, "default", default=cfg)
    check(eng.n_shards == 1, f"engine has {eng.n_shards} shards")

    # -- phase 2: merged-index build --------------------------------------
    t0 = time.perf_counter()
    merged = eng.merged_index(X)
    jax.block_until_ready((merged.nbrs, merged.mean_nbr_dist))
    build_s = time.perf_counter() - t0
    say(f"build: merged index G_(X∪Y) over {merged.vecs.shape[0]} rows in "
        f"{build_s:.1f}s (cold, compiles included); table on "
        f"{sorted(d.platform for d in merged.vecs.devices())}")

    # -- phase 3: joins -----------------------------------------------------
    for tag, jcfg in (
            ("es_mi_adapt/off", cfg),
            ("es_mi_adapt/sq8", dataclasses.replace(cfg, quant="sq8")),
            ("nlj/off", dataclasses.replace(cfg, method="nlj"))):
        res, comp_s, warm_s = timed_twice(lambda: eng.join(X, jcfg))
        say(f"join {tag}: {len(res.pairs)} pairs, warm {warm_s:.2f}s, "
            f"first-call compile {comp_s:.1f}s, ood={res.stats.n_ood}, "
            f"overflow={res.stats.n_overflow}")
        judge(f"join {tag}", res.pairs, ref, X, Y,
              exact=jcfg.method == "nlj")

    # -- phase 4: JoinService ------------------------------------------------
    svc = JoinService(ServiceConfig(buckets=(64, 128, 256)))
    svc.load("sift", Y, default=cfg)
    t0 = time.perf_counter()
    n_warm = svc.warmup("sift", thetas=[theta], methods=("es_sws",),
                        quants=("off", "sq8"))
    tenant = svc.engine("sift")
    say(f"serve: warmup {n_warm} joins in {time.perf_counter() - t0:.1f}s "
        f"(G_Y build {tenant.build_seconds:.1f}s of it)")
    rng = np.random.default_rng(args.seed)
    reqs = []
    for uid in range(8):
        n = int(rng.integers(64, 257))
        lo = int(rng.integers(0, ref.n_sample - n + 1))
        reqs.append((lo, JoinRequest(uid=uid, tenant="sift", X=X[lo:lo + n],
                                     theta=theta,
                                     quant=("off", "sq8")[uid % 2])))
    for _, r in reqs:
        check(svc.submit(r), f"request {r.uid} rejected: "
                             f"{svc.failed.get(r.uid)}")
    c0 = obs_metrics.compile_count()
    t0 = time.perf_counter()
    done = svc.run()
    serve_s = time.perf_counter() - t0
    delta = obs_metrics.compile_count() - c0
    nq = sum(len(r.X) for _, r in reqs)
    say(f"serve: {len(reqs)} requests, {nq} queries in {serve_s:.2f}s, "
        f"compiles after warmup: {delta}")
    check(delta == 0, f"serve recompiled {delta} times after warmup")
    for lo, r in reqs:
        sj = done[r.uid]
        check(sj.ok, f"request {r.uid} failed: {svc.failed.get(r.uid)}")
        local = np.asarray(sorted(sj.pair_set_local()),
                           np.int64).reshape(-1, 2)
        local[:, 0] += lo
        judge(f"serve uid={r.uid} quant={r.quant} "
              f"method={svc.plan(r).method}", local, ref, X, Y,
              exact=False, q_lo=lo, q_hi=lo + len(r.X))

    # -- phase 6: device evidence ---------------------------------------------
    hlo = wave_epilogue_hlo(eng, X, dataclasses.replace(cfg, quant="sq8"))
    n_cc = hlo.count("tpu_custom_call")
    say(f"compiled sq8 wave epilogue: {n_cc} tpu_custom_call ops")
    check(n_cc > 0, "the wave epilogue holds no Pallas kernel")


def run_sharded(args, ds, theta, ref, n_chips: int) -> None:
    import jax

    from repro.configs.vectorjoin import make_engine, preset

    X, Y = ds.X, ds.Y
    devs = jax.devices()
    for method in ("es_mi", "nlj"):
        cfg = preset(method, theta=theta)
        eng = make_engine(Y, "default", default=cfg, n_shards=n_chips)
        t0 = time.perf_counter()
        res, comp_s, warm_s = timed_twice(lambda: eng.join(X, cfg))
        say(f"sharded {method} x{eng.n_shards}: {len(res.pairs)} pairs, "
            f"warm {warm_s:.2f}s, first-call compile {comp_s:.1f}s, "
            f"build {eng.build_seconds:.1f}s, total "
            f"{time.perf_counter() - t0:.1f}s")
        judge(f"sharded {method}", res.pairs, ref, X, Y,
              exact=method == "nlj")
        sharded = set(map(tuple, res.pairs.tolist()))
        # every device must hold its share of the table between waves,
        # not only device 0 with the rest streamed in per wave
        share = Y.nbytes // n_chips
        for d in devs:
            m = memory(d)
            say(f"  device {d.id}: in use {m['bytes_in_use']} B, peak "
                f"{m['peak_bytes_in_use']} B (table share {share} B)")
            check(m["bytes_in_use"] >= 0.9 * share,
                  f"device {d.id} holds less than its share of the table")

        single = make_engine(Y, "default", default=cfg, n_shards=1)
        t0 = time.perf_counter()
        one = single.join(X, cfg)
        say(f"single-device {method}: {len(one.pairs)} pairs in "
            f"{time.perf_counter() - t0:.1f}s (build included)")
        judge(f"single {method}", one.pairs, ref, X, Y,
              exact=method == "nlj")
        mine = set(map(tuple, one.pairs.tolist()))
        say(f"sharded vs single {method}: {len(sharded - mine)} only "
            f"sharded, {len(mine - sharded)} only single")
        if method == "nlj":
            diff = sample_pairs(res.pairs, ref.n_sample) ^ \
                sample_pairs(one.pairs, ref.n_sample)
            check(not (diff - ref.band),
                  "sharded nlj differs from single-device off the band")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded path on a 2x2 host, and nothing "
                         "else")
    ap.add_argument("--n-data", type=int, default=500_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    from jax import monitoring

    # -- phase 1: the device -------------------------------------------------
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"[smoke] no TPU found: JAX reports {d0.platform!r} devices",
              file=sys.stderr)
        return 1
    say(f"device: {d0.device_kind} x{len(devs)} ({d0.platform}), "
        f"jax {jax.__version__}, compile cache {cache_dir}")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devs)} devices")
    monitoring.register_event_duration_secs_listener(_on_compile)

    from repro.quant.cascade import MATMUL_GUARD

    t_start = time.perf_counter()
    ds, theta = make_data(args)
    t0 = time.perf_counter()
    ref = reference(ds.X, ds.Y, theta, N_SAMPLE, MATMUL_GUARD)
    say(f"reference: float64 on the host, {N_SAMPLE} queries x "
        f"{ds.Y.shape[0]} rows: {len(ref.truth)} pairs, {len(ref.band)} in "
        f"the ulp band at θ ({time.perf_counter() - t0:.1f}s)")

    if args.chips == 4:
        run_sharded(args, ds, theta, ref, args.chips)
    else:
        run_single(args, ds, theta, ref)
    check_impls()
    say(f"peak_bytes_in_use: {memory(d0)['peak_bytes_in_use']} "
        f"(device {d0.id}); total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
