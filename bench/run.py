#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 bench/run.py --workload sift1m.join --seed 7 --seconds 10 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<name>.json``, whose ``regime`` and ``metric`` name
``bench/regimes/<name>.py`` and ``bench/spaces/<name>.py``) and a traffic
mix (``bench/traffic/<name>.json``, whose ``driver`` names
``bench/drivers/<name>.py``); the metrics are read by
``bench/metrics/<name>.py`` (``harness/registry.py``). A run: checks
that JAX sees a TPU with as many chips as the cell asks for (no
fallback); sets up data, index and a warm-up of the window's shapes
(``setup_s``); measures ``--seconds`` of traffic with no compile inside
the window; reads the device's peak memory; frees the program's state;
and judges every answer of the window against the plain float64
reference. With ``--trace 1`` the window is a shorter one under the
profiler, and the per-layer metrics are printed instead of the
end-to-end ones.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompiledInWindow(RuntimeError):
    """A program was compiled or loaded inside the measured window."""


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {d0.platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "devices": devs[:chips]}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def checks(tally, cfg: dict) -> dict:
    """Each number compared, with its limit and the side that passes."""
    return {
        "beyond_theta": {"value": tally.beyond, "limit": 0, "pass": "<="},
        "duplicates": {"value": tally.duplicates, "limit": 0, "pass": "<="},
        "recall": {"value": tally.recall, "limit": cfg["recall_floor"],
                   "pass": ">="},
    }


def passed(c: dict) -> bool:
    v, lim = c["value"], c["limit"]
    if not isinstance(v, (int, float)) or math.isnan(v):
        return False
    return v <= lim if c["pass"] == "<=" else v >= lim


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             t_start: float | None = None, keep_trace: str | None = None
             ) -> dict:
    """One run of one cell; returns the result line's object."""
    from harness.compiles import CompileCounter
    from harness.record import RunRecord
    from harness.registry import Registry

    t_start = T_START if t_start is None else t_start
    reg = Registry(root)
    cell = reg.cell(workload)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = device_info(cell.chips, require_tpu)
    if require_tpu:
        reg.peaks(dev["kind"])       # an unknown device is an error
    counter = CompileCounter()

    import jax
    window_s = (float(cell.traffic["trace_seconds"]) if trace
                else float(seconds))
    drv = cell.driver(cell, seed, window_s)
    drv.setup()
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    c0 = counter.count
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                drv.window()
        finally:
            if trace:
                jax.profiler.stop_trace()
        compiled = counter.count - c0
        if compiled:
            raise CompiledInWindow(
                f"{compiled} programs compiled or loaded in the window")
        mem = memory_peak(dev["devices"])
        drv.release()
        tally = drv.check()
        records = reduction = None
        if trace:
            from harness import trace as tr
            path = tr.find_xplane(tdir)
            if keep_trace:
                Path(keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, Path(keep_trace) / path.name)
            records = tr.load_xplane(path)
            reduction = tr.reduce(records)
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)

    rec = RunRecord(workload, cell.cfg, cell.traffic, drv, setup_s, mem,
                    tally, records, reduction)
    entries = reg.per_layer(workload) if trace else reg.end_to_end(workload)
    metrics = {}
    for m in entries:
        v = reg.metric(m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # a join call that fails ends the run, so every attempted query that
    # reaches the result line was answered
    attempted = sum(c.n_queries for c in drv.calls)
    cks = checks(tally, cell.cfg)
    out = {"correct": all(passed(c) for c in cks.values()),
           "attempted": attempted, "failed": 0, "metrics": metrics,
           "device": {"platform": dev["platform"], "kind": dev["kind"],
                      "count": dev["count"], "memory_peak_bytes": mem}}
    if trace:
        out["device"]["busy_s"] = reduction.busy_s
        out["device"]["window_s"] = reduction.window_s
        out["breakdown"] = reduction.breakdown()
    out["checks"] = cks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1: also copy the raw .xplane.pb here")
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), keep_trace=args.keep_trace)
    except (NoChip, CompiledInWindow) as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"[bench] check {name}: {c['value']} (passes if "
              f"{c['pass']} {c['limit']})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
