"""The traversal's and the wave pipeline's per-layer metrics
(``gather_use.join``, ``lane_use.join``, ``wave_host_ms.join``): each
reader on a hand-built run record, each reading nothing from a program
that lacks what it reads, and all three in a tiny traced run."""
import time
from types import SimpleNamespace

import pytest

from benchtiny import ROOT
from harness.drivers import Call, OneShot
from harness.record import RunRecord
from harness.registry import Registry
from harness.trace import Event, Records

import run as bench_run

NEW = ("gather_use.join", "lane_use.join", "wave_host_ms.join")
SEED = 2**31 + 29


def reader(name):
    return Registry(ROOT).metric(name).read


def record(stats, host=None, wave_size=4):
    calls = [Call(0.0, 1.0, 8, st, None) for st in stats]
    driver = SimpleNamespace(calls=calls,
                             jcfg=SimpleNamespace(wave_size=wave_size))
    records = Records([], host) if host is not None else None
    return RunRecord("sift1m.join", {}, {}, driver, 1.0, 0, None, records)


def test_gather_use_is_kept_over_probed_slots():
    stats = [SimpleNamespace(n_dist=30, n_slots=400),
             SimpleNamespace(n_dist=10, n_slots=100)]
    assert reader("gather_use.join")(record(stats)) == pytest.approx(0.08)


def test_lane_use_is_active_over_wave_lane_iterations():
    # wave_size 4: 10 + 6 active lane-iterations over 4 x (5 + 3)
    stats = [SimpleNamespace(n_lane_iters=10, n_iters=5),
             SimpleNamespace(n_lane_iters=6, n_iters=3)]
    assert reader("lane_use.join")(record(stats)) == pytest.approx(0.5)


def test_wave_host_ms_is_wave_span_time_less_fetches_per_wave():
    ms = 1e6                                  # ns per ms
    host = [
        Event("bench.window", 0, 100 * ms),
        Event("join", 1 * ms, 98 * ms),       # no wave span: not counted
        # wave 0
        Event("wave/launch", 2 * ms, 1 * ms),
        Event("wave/band", 10 * ms, 4 * ms),
        Event("wave/fetch", 10 * ms, 3 * ms),     # waits inside band
        Event("wave/assemble", 20 * ms, 10 * ms),
        Event("wave/fetch", 20 * ms, 6 * ms),
        # wave 1, its assembly cut by the window's end
        Event("wave/launch", 40 * ms, 2 * ms),
        Event("wave/assemble", 96 * ms, 8 * ms),
        Event("wave/fetch", 96 * ms, 1 * ms),
        # outside the window: not counted
        Event("wave/launch", 120 * ms, 5 * ms),
    ]
    # own: 1 + 4 + 10 + 2 + 4 = 21 ms; fetches: 3 + 6 + 1 = 10 ms
    got = reader("wave_host_ms.join")(record([], host))
    assert got == pytest.approx(11 / 2)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_from_a_program_without_it(name):
    """The parent's program has no slot or lane counter and no program
    spans: each reader returns None and raises nothing."""
    old = [SimpleNamespace(n_dist=5, n_iters=2)]
    host = [Event("bench.window", 0, 100),
            Event("bench.engine.join", 1, 98)]
    assert reader(name)(record(old, host)) is None


def test_tiny_traced_run_reports_the_three_metrics(tiny_root, monkeypatch):
    seen = []
    release = OneShot.release

    def keep_calls(self):
        seen.extend(self.calls)
        release(self)

    monkeypatch.setattr(OneShot, "release", keep_calls)
    out = bench_run.run_cell("sift1m.join", SEED, 0.5, True,
                             root=tiny_root, require_tpu=False,
                             t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    got = {k: out["metrics"][k]["value"] for k in NEW}
    assert 0 < got["gather_use.join"] <= 1
    assert got["gather_use.join"] == pytest.approx(
        sum(c.stats.n_dist for c in seen)
        / sum(c.stats.n_slots for c in seen))
    assert 0 < got["lane_use.join"] <= 1
    assert got["wave_host_ms.join"] > 0
