import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + (
    os.environ.get("REPRO_BENCH_DEVICES", "4"))

# Worker for bench_overall.run_sharded: needs N forced host devices, so it
# must own the process (jax locks the device count at first init). Runs one
# (n_shards, method, quant, N_y) cell through the mesh-driver JoinEngine
# twice — the first pass pays index builds and compiles, the second is the
# reported steady-state wall-clock — and prints one JSON line with the
# per-transfer-class and per-collective byte meters.
import argparse
import json
import time

import jax
import numpy as np

from repro.core import exact_join_pairs
from repro.core.types import JoinConfig, JoinResult, JoinStats, recall
from repro.data.vectors import make_dataset
from repro.engine import JoinEngine


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-data", type=int, required=True)
    ap.add_argument("--n-query", type=int, default=256)
    ap.add_argument("--dim", type=int, default=48)
    ap.add_argument("--shards", type=int, required=True)
    ap.add_argument("--method", default="es_mi")
    ap.add_argument("--quant", default="off")
    ap.add_argument("--theta", type=float, required=True)
    ap.add_argument("--wave", type=int, default=128)
    args = ap.parse_args(argv)

    ds = make_dataset("manifold", n_data=args.n_data, n_query=args.n_query,
                      dim=args.dim, seed=5)
    cfg = JoinConfig(method=args.method, theta=args.theta,
                     wave_size=args.wave, quant=args.quant)
    eng = JoinEngine(ds.Y, build_kw=dict(k=32, degree=24),
                     n_shards=args.shards)
    eng.join(ds.X, cfg)  # builds + compiles
    t0 = time.perf_counter()
    res = eng.join(ds.X, cfg)
    dt = time.perf_counter() - t0

    tr = exact_join_pairs(ds.X, ds.Y, args.theta)
    rec = recall(JoinResult(pairs=res.pairs, stats=JoinStats()), tr)
    st = res.stats
    occ = np.asarray(st.band_occ_per_shard or (0,), dtype=np.float64)
    n_waves = max(-(-args.n_query // args.wave), 1)
    host_bytes = st.bytes_feedback + st.bytes_band + st.bytes_assembly
    print(json.dumps(dict(
        platform=jax.devices()[0].platform, n_shards=args.shards,
        n_data=args.n_data, method=args.method,
        quant=args.quant, seconds=dt, recall=rec, pairs=len(res.pairs),
        n_dist=int(st.n_dist),
        bytes_feedback=int(st.bytes_feedback),
        bytes_band=int(st.bytes_band),
        bytes_assembly=int(st.bytes_assembly),
        bytes_allgather=int(st.bytes_allgather),
        bytes_ppermute=int(st.bytes_ppermute),
        bytes_psum=int(st.bytes_psum),
        host_bytes_per_wave=host_bytes / n_waves,
        shard_band_imbalance=(float(occ.max() / occ.mean())
                              if occ.mean() > 0 else 1.0))))


if __name__ == "__main__":
    main()
