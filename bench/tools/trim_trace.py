#!/usr/bin/env python3
"""Store a profiler trace as the plain records the reduction reads
(``harness/trace.py``), trimmed to the device operations and the host
spans of the threads that name them:

    python3 bench/tools/trim_trace.py trace.xplane.pb bench/traces/x.json

Keeps every device operation, the benchmark's ``bench.*`` spans, and the
host events of the runtime's main thread."""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness.trace import load_xplane  # noqa: E402


def trim(src: str, dst: str, max_host: int = 4000) -> None:
    rec = load_xplane(src)
    main = [e for e in rec.host
            if e.name.startswith("bench.") or "|main/" in e.plane]
    rec.host = main[:max_host]
    Path(dst).parent.mkdir(parents=True, exist_ok=True)
    Path(dst).write_text(json.dumps(rec.to_json(), separators=(",", ":")))


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2])
