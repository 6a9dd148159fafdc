"""The ``t2i`` deployment's parts: the configuration, the ``modality_gap``
regime, the inner-product space and the per-layer reader run and judge
correctly at a tiny size on the CPU."""
import json
import time

import numpy as np
import pytest

from benchtiny import BENCH, make_tiny
from harness.control import control_pairs
from harness.data import thresholds
from harness.reference import beyond_theta, reference
from harness.record import RunRecord
from harness.registry import Registry

import run as bench_run

SEED = 2**31 + 11
TINY_T2I = dict(n_data=2000, n_query=96, dim=32)


@pytest.fixture(scope="module")
def t2i_root(tmp_path_factory):
    """The tiny benchmark with ``t2i`` cut to a CPU size, θ its tiny
    distribution's θ₁ as ``tools/calibrate.py`` takes it at full size."""
    root = make_tiny(tmp_path_factory.mktemp("t2i"))
    cfg = json.loads((BENCH / "configs" / "t2i.json").read_text())
    cfg.update(TINY_T2I)
    reg = Registry(root)
    Y, X = reg.regime(cfg["regime"]).draw(cfg, cfg["n_data"],
                                          cfg["n_query"])
    cfg["theta"] = float(thresholds(X, Y, reg.space("ip"), lo_q=1e-3,
                                    sample=100_000)[0])
    (root / "bench" / "configs" / "t2i.json").write_text(json.dumps(cfg))
    return root


def test_t2i_cell_runs_correct(t2i_root):
    out = bench_run.run_cell("t2i.join", SEED, 0.5, False, root=t2i_root,
                             require_tpu=False, t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["checks"]["beyond_theta"]["value"] == 0
    assert {"join_qps", "recall", "setup_s"} <= set(out["metrics"])
    cell = Registry(t2i_root).cell("t2i.join")
    assert cell.space.__file__.endswith("ip.py")
    assert cell.cfg["theta"] < 0          # a pair joins when ⟨x, y⟩ > −θ


def test_t2i_traced_run_reads_the_hybrid_share(t2i_root):
    out = bench_run.run_cell("t2i.join", SEED, 0.5, True, root=t2i_root,
                             require_tpu=False, t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    share = out["metrics"]["hybrid_share.join"]["value"]
    assert 0.5 < share <= 1.0


def test_control_mask_differs_from_within(t2i_root):
    """The bfloat16 control joins other pairs than the float64 test (at
    the cell's own size on the chip it comes out not correct: PERF.md)."""
    cell = Registry(t2i_root).cell("t2i.join")
    drv = cell.driver(cell, SEED, 0.0)
    drv.make_data()
    low = control_pairs(drv.X, drv.dep.Y, drv.theta, cell.space)
    exact = reference(drv.X, drv.dep.Y, drv.theta, cell.space).truth
    assert set(map(tuple, low.tolist())) != exact


def test_ip_space_band_and_beyond_at_theta():
    """Hand-made pairs at θ: −⟨x, y⟩ = θ + t·band for t below, inside and
    past the band."""
    ip = Registry(BENCH.parent).space("ip")
    theta = -12.0
    x = np.array([[3.0, 4.0]])                       # ‖x‖ = 5
    base = np.array([[1.2, 1.6]])                    # ⟨x, base⟩ = 10
    scale = -theta / 10.0
    for t, beyond in ((-2.0, False), (0.0, False), (0.5, False),
                      (1.5, True), (3.0, True)):
        y = base * scale                             # −⟨x, y⟩ = θ
        width = ip.band(x, y)[0]
        assert width == pytest.approx(ip.GUARD * 5.0 * 2.0 * scale)
        y = y * (1 - t * width / -theta)             # −⟨x, y⟩ = θ + t·band
        d = -float(np.dot(x[0], y[0]))
        assert d == pytest.approx(theta + t * width, abs=1e-12)
        assert bool(ip.beyond(x, y, theta)[0]) is beyond, t
        assert bool(ip.within(x, y, theta)[0, 0]) is (t < 0), t
    assert ip.distance(x, base)[0] == -10.0
    # an emitted pair past the band counts, one inside it does not
    X = x.astype(np.float32)
    Y = np.concatenate([base * scale * (1 - 0.5 * 1e-6),     # in the band
                        base * scale * 0.5])                 # far past it
    assert beyond_theta(np.array([[0, 0], [0, 1]]), X, Y, theta, ip) == 1


def test_regime_is_deterministic_and_queries_lie_off_the_rows():
    reg = Registry(BENCH.parent)
    cfg = dict(json.loads((BENCH / "configs" / "t2i.json").read_text()),
               dim=32)
    regime = reg.regime(cfg["regime"])
    Y1, X1 = regime.draw(cfg, 3000, 200)
    Y2, X2 = regime.draw(cfg, 3000, 200)
    assert np.array_equal(Y1, Y2) and np.array_equal(X1, X2)
    assert Y1.dtype == X1.dtype == np.float32
    # the queries do not depend on the table's size
    assert np.array_equal(regime.draw(cfg, 500, 200)[1], X1)
    Y3, X3 = regime.draw(dict(cfg, shape_seed=cfg["shape_seed"] + 1),
                         3000, 200)
    assert not np.allclose(Y1, Y3) and not np.allclose(X1, X3)
    norms = np.linalg.norm(Y1, axis=1)
    # the rows' norms spread (≈ exp(0.25·N(0,1)) around the network's)
    assert np.std(np.log(norms)) > 0.2
    # the queries sit across the modality gap from the rows
    shift = np.linalg.norm(X1.mean(axis=0) - Y1.mean(axis=0))
    assert shift > 0.4 * np.median(norms)
    # and off the rows' manifold: farther from their nearest row than
    # rows are from theirs
    def nn(a, b):
        d = np.linalg.norm(a[:, None, :] - b[None, :, :], axis=2)
        return np.median(d.min(axis=1))
    assert nn(X1, Y1[200:]) > 1.2 * nn(Y1[:200], Y1[200:])


def test_hybrid_share_reader():
    from harness.driver import Call
    from repro.core.types import JoinStats
    reg = Registry(BENCH.parent)
    m = reg.metric("hybrid_share.join")

    def run(stats):
        drv = type("D", (), {"calls": [Call(0, 1, 8, s, None)
                                       for s in stats]})()
        return RunRecord("t2i.join", {}, {}, drv, 0.0, 0, None)
    got = m.read(run([JoinStats(n_lane_iters=40, n_hybrid_lane_iters=30),
                      JoinStats(n_lane_iters=60, n_hybrid_lane_iters=20)]))
    assert got == pytest.approx(0.5)
    assert m.read(run([JoinStats()])) is None

    class Old:                     # a program without the hybrid counter
        n_lane_iters = 5
    assert m.read(run([Old()])) is None
