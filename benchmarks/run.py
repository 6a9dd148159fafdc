"""Benchmark runner — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale ci|full] [--only NAME]

Sections map to the paper (DESIGN §7): Fig 9 join sizes, Fig 10 overall,
Fig 11 queue sizes, Fig 12 breakdown, Fig 13 offline overhead, Fig 14
scalability, Fig 15 index type, plus the beyond-paper distributed join.
"""
from __future__ import annotations

import argparse
import time
import traceback

from types import SimpleNamespace

from benchmarks import (bench_breakdown, bench_distributed, bench_index_type,
                        bench_join_size, bench_offline, bench_overall,
                        bench_queue_size, bench_scalability)
from benchmarks.common import emit
from repro.launch.compile_cache import enable_compile_cache

_quant = SimpleNamespace(run=lambda scale: bench_breakdown.run_quant(
    "full_hd" if scale == "full" else "ci_hd"))

BENCHES = [
    ("fig9_join_size", bench_join_size),
    ("fig10_overall", bench_overall),
    ("fig11_queue_size", bench_queue_size),
    ("fig12_breakdown", bench_breakdown),
    ("quant_bytes", _quant),           # f32-vs-sq8 kernel time & bytes
    ("fig13_offline", bench_offline),
    ("fig14_scalability", bench_scalability),
    ("fig15_index_type", bench_index_type),
    ("distributed_join", bench_distributed),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=("ci", "full"), default="ci")
    ap.add_argument("--only")
    args = ap.parse_args(argv)
    enable_compile_cache()
    failed = []
    for name, mod in BENCHES:
        if args.only and args.only not in name:
            continue
        print(f"\n== {name} (scale={args.scale}) ==", flush=True)
        t0 = time.time()
        try:
            emit(mod.run(args.scale))
            print(f"-- {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001 — keep the suite running
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"\nFAILED: {failed}")
        return 1
    print("\nall benches OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
