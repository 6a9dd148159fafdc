"""The reduction from a profiler trace to busy time, idle gaps and kernel
time, checked on traces recorded on a v5e (trimmed copies under
``bench/traces/``) against an independent sweep, and on hand-made
records."""
import json

import pytest

from benchtiny import BENCH
from harness.trace import Event, Records, reduce, union

TRACES = sorted((BENCH / "traces").glob("*.json"))


def sweep_busy(intervals, lo, hi):
    """Busy ns by counting open intervals at each endpoint (a different
    algorithm from ``union``)."""
    pts = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            pts += [(s, 1), (e, -1)]
    pts.sort()
    busy, depth, t0 = 0.0, 0, None
    for t, step in pts:
        if depth == 0 and step == 1:
            t0 = t
        depth += step
        if depth == 0:
            busy += t - t0
    return busy


def load(path):
    return Records.from_json(json.loads(path.read_text()))


def test_stored_traces_exist():
    assert TRACES, "no recorded chip trace under bench/traces"


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_busy_and_gaps_match_independent_sweep(path):
    rec = load(path)
    red = reduce(rec)
    lo, hi = red.window
    assert hi > lo and red.devices >= 1
    plane = sorted({e.plane for e in rec.device})[0]
    evs = [(e.start, e.end) for e in rec.device if e.plane == plane]
    busy = sweep_busy(evs, lo, hi)
    assert sum(e - s for s, e in red.busy[0]) == pytest.approx(busy)
    # busy and idle tile the window
    idle = sum(e - s for s, e, _ in red.gaps)
    assert busy + idle == pytest.approx(hi - lo)
    assert red.busy_s == pytest.approx(busy * 1e-9)
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_kernel_sums_match_direct_sum(path):
    rec = load(path)
    red = reduce(rec)
    lo, hi = red.window
    direct = {}
    for e in rec.device:
        if e.start >= lo and e.end <= hi:
            direct[e.name] = direct.get(e.name, 0.0) + e.dur
    for name, ns in direct.items():
        assert red.op_ns[name] == pytest.approx(ns)
    # self time never exceeds total time, and the outermost operations'
    # self times plus their bodies' add up to the busy time at most
    for name, ns in red.self_ns.items():
        assert ns <= red.op_ns.get(name, 0.0) + 1e-6
    assert sum(red.self_ns.values()) == pytest.approx(
        sum(e - s for s, e in red.busy[0]), rel=1e-6)
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_hand_made_records():
    dev = [Event("%a.1 = f32[2]{0} fusion(f32[2]{0} %x)", 0, 10, "d0"),
           Event("%w.2 = f32[2]{0} while(f32[2]{0} %x)", 20, 30, "d0"),
           Event("%b.3 = f32[2]{0} fusion(f32[2]{0} %x)", 25, 5, "d0"),
           Event("%a.1 = f32[2]{0} fusion(f32[2]{0} %x)", 60, 10, "d0")]
    host = [Event("bench.window", 0, 100, "h|main"),
            Event("numpy work", 12, 6, "h|main"),
            Event("outer", 50, 20, "h|main"),
            Event("inner", 52, 4, "h|main")]
    red = reduce(Records(dev, host))
    assert red.window == (0, 100)
    assert red.busy == [[(0, 10), (20, 50), (60, 70)]]
    assert [(s, e, lab) for s, e, lab in red.gaps] == [
        (10, 20, "numpy work"), (50, 60, "inner"), (70, 100, "no host span")]
    assert red.op_ns["%a.1 = f32[2]{0} fusion(f32[2]{0} %x)"] == 20
    assert red.self_ns["%w.2 = f32[2]{0} while(f32[2]{0} %x)"] == 25
    assert red.busy_s == pytest.approx(50e-9)
    assert union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
