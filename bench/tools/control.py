#!/usr/bin/env python3
"""Run the control in a cell's place and print what the benchmark's
comparison says of it, one line per seed:

    python3 bench/tools/control.py --workload sift1m.join --seeds 5 6 7

Data and queries are made exactly as a run of the cell makes them; the
answer is the bfloat16 reference's (``harness/control.py``) instead of
the program's. No program code runs."""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from run import checks, passed  # noqa: E402
from harness.control import control_pairs  # noqa: E402
from harness.driver import Call  # noqa: E402
from harness.registry import Registry  # noqa: E402


def control_run(reg: Registry, workload: str, seed: int) -> dict:
    cell = reg.cell(workload)
    cfg = cell.cfg
    drv = cell.driver(cell, seed, 0.0)
    drv.make_data()
    t0 = time.perf_counter()
    pairs = control_pairs(drv.X, drv.dep.Y, drv.theta, cell.space,
                          precision=cfg.get("precision", "float32"))
    drv.calls = [Call(0.0, 1.0, len(drv.X), None, pairs)]
    t_ctl = time.perf_counter() - t0
    tally = drv.check()
    cks = checks(tally, cfg)
    return {"workload": workload, "seed": seed,
            "correct": all(passed(c) for c in cks.values()),
            "control_s": t_ctl, "checks": cks}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    reg = Registry(BENCH.parent)
    for seed in args.seeds:
        print(json.dumps(control_run(reg, args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
