"""Helpers for the benchmark's own tests: a copy of the benchmark whose
configurations are cut to a size the CPU runs in seconds. Every other part
(traffic mixes, metric readers, regimes, spaces, drivers, and any part
directory added later) is the real one."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"sift1m": dict(n_data=2000, n_query=160, dim=16, theta=2.4),
        "gist1m": dict(n_data=2000, n_query=64, dim=40, theta=6.0)}
TINY_TRAFFIC = {"check_queries": 64, "trace_seconds": 0.5}


def make_tiny(dst: Path) -> Path:
    """A benchmark root at ``dst`` with the real parts and tiny configs."""
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "configs"))
    (dst / "bench" / "configs").mkdir()
    for name, over in TINY.items():
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        cfg.update(over)
        (dst / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for path in (dst / "bench" / "traffic").glob("*.json"):
        tr = json.loads(path.read_text())
        tr.update(TINY_TRAFFIC)
        path.write_text(json.dumps(tr))
    return dst
