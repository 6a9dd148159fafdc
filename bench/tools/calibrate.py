#!/usr/bin/env python3
"""Print θ₁ of each named configuration's distribution (the ``theta`` its
file records), drawn by its regime and measured in its space:
``python3 bench/tools/calibrate.py sift1m gist1m``."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness.data import calibrate_theta  # noqa: E402
from harness.registry import Registry  # noqa: E402

reg = Registry(BENCH.parent)
for name in sys.argv[1:]:
    cfg, regime, space = reg.deployment(name)
    print(name, repr(calibrate_theta(cfg, regime, space)), flush=True)
