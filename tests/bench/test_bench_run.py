"""Whole runs of each cell at a tiny size on the CPU, with the harness's
look for a chip skipped: the result line's keys, the checks, the faults
that the comparison has to catch, and the control."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchtiny import ROOT

import run as bench_run

CELLS = ["sift1m.join", "gist1m.join"]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
SEED = 2**31 + 11


def run_tiny(root, cell, trace=False, seed=SEED):
    return bench_run.run_cell(cell, seed, 0.5, trace, root=root,
                              require_tpu=False,
                              t_start=time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct_with_contract_keys(tiny_root, cell):
    out = run_tiny(tiny_root, cell)
    assert set(out) == KEYS and list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    from harness.registry import Registry
    # every end-to-end metric of the cell reads something, but the CPU
    # reports no peak memory
    assert set(out["metrics"]) == {
        m["name"] for m in Registry(tiny_root).end_to_end(cell)} - {
        "hbm_peak_mb"}
    assert {"setup_s", "recall"} <= set(out["metrics"])
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert out["checks"]["beyond_theta"]["value"] == 0


def oneshot(root):
    """The one-shot driver class that runs of cells under ``root`` use."""
    from harness.registry import Registry
    return Registry(root).driver("oneshot").OneShot


def test_same_seed_same_inputs(tiny_root):
    from harness.registry import Registry
    cell = Registry(tiny_root).cell("sift1m.join")

    def inputs(seed):
        d = cell.driver(cell, seed, 1.0)
        d.make_data()
        return d.dep.Y, d.X

    (y0, x0), (y1, x1) = inputs(SEED), inputs(SEED)
    assert np.array_equal(y0, y1) and np.array_equal(x0, x1)
    y2, x2 = inputs(SEED + 1)
    # another seed: the same queries, the same table in another order
    assert np.array_equal(x2, x0) and not np.array_equal(y2, y0)
    assert np.array_equal(np.unique(y2, axis=0), np.unique(y0, axis=0))


def test_each_call_of_the_window_is_judged(tiny_root, monkeypatch):
    """Every call of the window counts in the tally, identical answers
    included, and one changed answer among them is caught."""
    OneShot = oneshot(tiny_root)
    join_window = OneShot.window

    def window(self):
        join_window(self)
        while len(self.calls) < 3:
            self.calls.append(self.calls[-1])
        bad = self.calls[-1]
        self.calls[-1] = type(bad)(bad.t0, bad.t1, bad.n_queries, bad.stats,
                                   _alter_one(bad.pairs, len(self.dep.Y)))

    monkeypatch.setattr(OneShot, "window", window)
    out = run_tiny(tiny_root, "sift1m.join")
    assert not out["correct"], out["checks"]
    assert out["attempted"] >= 3 * 160


def test_compile_in_window_ends_the_run(tiny_root, monkeypatch):
    import jax
    import jax.numpy as jnp
    OneShot = oneshot(tiny_root)
    join_window = OneShot.window

    def window(self):
        join_window(self)
        jax.jit(lambda v: v * 3 + len(self.calls))(jnp.ones(7))

    monkeypatch.setattr(OneShot, "window", window)
    with pytest.raises(bench_run.CompiledInWindow):
        run_tiny(tiny_root, "gist1m.join")


def test_trace_run_reports_per_layer_and_device_window(tiny_root,
                                                      tmp_path):
    out = bench_run.run_cell("sift1m.join", SEED, 0.5, True,
                             root=tiny_root, require_tpu=False,
                             t_start=time.perf_counter(),
                             keep_trace=str(tmp_path / "kept"))
    # the raw trace is kept for bench/tools/trim_trace.py
    assert list((tmp_path / "kept").glob("*.xplane.pb"))
    assert out["correct"]
    assert {"build_s", "dist_per_query"} <= set(out["metrics"])
    assert "join_qps" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


def _drop_half(pairs):
    return pairs[pairs[:, 0] % 2 == 0]


def _alter_one(pairs, n_rows):
    out = pairs.copy()
    if len(out):
        out[0, 1] = (out[0, 1] + n_rows // 2) % n_rows
    return out


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
def test_broken_path_is_not_correct(tiny_root, monkeypatch, cell, fault):
    """The timed path broken underneath the harness: the run has to come
    out not correct."""
    from repro.engine.engine import JoinEngine

    def broken(pairs, eng):
        if fault == "half_left_out":
            return _drop_half(pairs)
        return _alter_one(pairs, int(eng.Y.shape[0]))

    join = JoinEngine.join

    def bad_join(self, X, cfg=None, **kw):
        res = join(self, X, cfg, **kw)
        res.pairs = broken(np.asarray(res.pairs), self)
        return res

    monkeypatch.setattr(JoinEngine, "join", bad_join)
    out = run_tiny(tiny_root, cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell):
    """The reference in bfloat16 in the program's place fails the
    comparison (at the cells' own sizes on the chip: PERF.md)."""
    sys.path.insert(0, str(ROOT / "bench" / "tools"))
    from control import control_run
    from harness.registry import Registry
    out = control_run(Registry(tiny_root), cell, SEED)
    assert not out["correct"], out["checks"]


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "sift1m.join", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_unknown_workload_fails(tiny_root):
    with pytest.raises(KeyError):
        run_tiny(tiny_root, "nope.join")


def test_checks_judge_each_number_against_its_limit():
    ok = {"value": 0.95, "limit": 0.9, "pass": ">="}
    assert bench_run.passed(ok)
    assert not bench_run.passed(dict(ok, value=0.85))
    assert not bench_run.passed(dict(ok, value=float("nan")))
    assert bench_run.passed({"value": 0, "limit": 0, "pass": "<="})
    assert not bench_run.passed({"value": 1, "limit": 0, "pass": "<="})
