#!/usr/bin/env python3
"""Print θ₁ of each named configuration's distribution (the ``theta`` its
file records): ``python3 bench/tools/calibrate.py sift1m gist1m``."""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness.data import calibrate_theta  # noqa: E402

for name in sys.argv[1:]:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    print(name, repr(calibrate_theta(cfg)), flush=True)
