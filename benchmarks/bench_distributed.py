"""Beyond paper: the distributed (shard_map) MI join.

Verifies X ⋈ Y == ∪ₛ (X ⋈ Yₛ) numerically — recall must be
shard-count-independent — and reports per-wave throughput. Each shard
count runs in a subprocess with that many forced host devices (one shard
per device, as on the production mesh; the in-process mesh here has a
single CPU device). The children run on the CPU (``JAX_PLATFORMS=cpu``,
so none of them reaches for a chip the parent may hold), and every row
says so in ``platform``. The production-mesh version is exercised by the
dry-run join cells (launch/dryrun.py --join).
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import emit


def run(scale: str = "ci") -> list[dict]:
    n = 8_000 if scale == "ci" else 100_000
    rows = []
    for n_shards in (1, 2, 4):
        env = dict(os.environ, REPRO_BENCH_DEVICES=str(n_shards),
                   PYTHONPATH="src", JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks._distributed_worker",
             str(n), str(n_shards)],
            capture_output=True, text=True, env=env, check=True)
        line = out.stdout.strip().splitlines()[-1]
        s, dt, rec, pairs, nd = line.split(",")
        rows.append(dict(platform="cpu", n_shards=int(s),
                         seconds=float(dt),
                         recall=float(rec), pairs=int(pairs),
                         n_dist=int(nd)))
    return rows


def main(scale: str = "ci") -> None:
    emit(run(scale))


if __name__ == "__main__":
    main()
