"""Golden equivalence and streaming regressions across quant modes.

Two contracts, asserted uniformly over ``QUANT_MODES``:

  * **Golden equivalence** — on a fixed-seed dataset, every compressed
    mode (``sq8``, ``sketch8``, ``pdx8``, ``sketchpdx8``) and ``off``
    emit the *identical* pair set at equal search budget across the NLJ,
    search (exhaustive ``index``), MI, and 2-shard paths. The budget is
    chosen so the f32 run reaches the exact truth; the quantized runs
    must then match it bit-for-bit. PDX modes additionally prove
    ``early_exit`` on == off (pair set and re-rank survivor count), with
    a regression floor that exit genuinely skips dimensions.
  * **Streaming regression** — multiple ``submit()`` batches under each
    mode produce the same pair set as a one-shot ``join()``, and
    ``reset_stream()`` clears every piece of carry state (resubmitting
    after a reset reproduces the first run exactly).

CI runs this module as a quant-mode matrix: setting ``REPRO_QUANT_MODE``
to one of the modes narrows the parametrization to that mode (each CI
matrix leg publishes its own junit XML).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import JoinConfig, TraversalConfig, exact_join_pairs
from repro.core.types import QUANT_MODES
from repro.data.vectors import make_dataset, thresholds
from repro.engine import JoinEngine

_ENV_MODE = os.environ.get("REPRO_QUANT_MODE")
if _ENV_MODE is not None and _ENV_MODE not in QUANT_MODES:
    # fail the CI matrix leg loudly — a typo'd mode silently running the
    # full cross-product would defeat per-mode triage
    raise RuntimeError(
        f"REPRO_QUANT_MODE={_ENV_MODE!r} is not one of {QUANT_MODES}")
MODES_UNDER_TEST = (_ENV_MODE,) if _ENV_MODE else QUANT_MODES

TC = TraversalConfig(beam_width=64, expand_per_iter=4, pool_cap=1024,
                     hybrid_beam=64, seeds_max=8, max_iters=2048)
BK = dict(k=24, degree=12)


def _cfg(method, theta, quant, wave=64):
    return JoinConfig(method=method, theta=theta, traversal=TC,
                      wave_size=wave, quant=quant)


@pytest.fixture(scope="module")
def golden_ds():
    return make_dataset("manifold", n_data=1500, n_query=96, dim=40,
                        seed=42)


@pytest.fixture(scope="module")
def golden_engine(golden_ds):
    return JoinEngine(golden_ds.Y, build_kw=BK)


@pytest.fixture(scope="module")
def golden_theta(golden_ds):
    return float(thresholds(golden_ds, 3)[0])


@pytest.fixture(scope="module")
def golden_truth(golden_ds, golden_theta):
    truth = set(map(tuple, exact_join_pairs(
        golden_ds.X, golden_ds.Y, golden_theta).tolist()))
    assert len(truth) > 0
    return truth


# -- golden equivalence -----------------------------------------------------


@pytest.mark.parametrize("quant", MODES_UNDER_TEST)
@pytest.mark.parametrize("method", ["nlj", "index", "es_mi"])
def test_golden_identical_pair_set(golden_ds, golden_engine, golden_theta,
                                   golden_truth, method, quant):
    """NLJ is exact by contract; ``index`` (search path, no early stop)
    and ``es_mi`` reach full recall at this budget on f32, so every
    quant mode must emit the identical — and exact — pair set."""
    if method != "nlj":
        r32 = golden_engine.join(golden_ds.X,
                                 _cfg(method, golden_theta, "off"))
        assert r32.pair_set() == golden_truth, "budget precondition"
    r = golden_engine.join(golden_ds.X, _cfg(method, golden_theta, quant))
    assert r.pair_set() == golden_truth, (method, quant)


_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    from repro.core import (JoinConfig, TraversalConfig, exact_join_pairs,
                            theta_band)
    from repro.data.vectors import make_dataset, thresholds
    from repro.engine import JoinEngine

    # 1501 % 2 != 0: the last shard carries far-away sentinel pad rows —
    # they must neither poison the sq8 scale grid nor the sketch center,
    # and the sketch tier must prune them by their own slack tables.
    ds = make_dataset("manifold", n_data=1501, n_query=64, dim=40, seed=42)
    theta = float(thresholds(ds, 3)[0])
    truth = set(map(tuple, exact_join_pairs(ds.X, ds.Y, theta).tolist()))
    assert len(truth) > 0
    # pairs within f32 rounding of θ may fall to either side
    band = theta_band(ds.X, ds.Y, theta)
    tc = TraversalConfig(beam_width=64, expand_per_iter=4, pool_cap=1024,
                         hybrid_beam=64, seeds_max=8, max_iters=2048)
    e2 = JoinEngine(ds.Y, build_kw=dict(k=24, degree=12), n_shards=2)
    sets = {}
    for quant in {modes}:
        cfg = JoinConfig(method="es_mi", theta=theta, traversal=tc,
                         wave_size=32, quant=quant)
        sets[quant] = e2.join(ds.X, cfg).pair_set()
        assert not ((sets[quant] ^ truth) - band), (
            quant, len(sets[quant] ^ truth))
    print("QUANT_MODES_SHARDED_OK")
""")


@pytest.mark.slow
def test_golden_identical_pair_set_2shard():
    """The 2-shard path emits the exact pair set under every quant mode
    (subprocess: forces 2 host devices without contaminating the suite)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    script = _SHARD_SCRIPT.replace("{modes}",
                                   repr(tuple(MODES_UNDER_TEST)))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "QUANT_MODES_SHARDED_OK" in r.stdout


# -- PDX early-exit equivalence ---------------------------------------------
#
# The PDX tier's whole claim: retiring lanes mid-vector on certified tail
# bounds changes wall-clock, never results. ``early_exit=False`` runs the
# same kernels as full slab scans with bit-identical survivor sums, so
# the emitted pair set AND the re-rank survivor count must match exactly.

PDX_MODES = tuple(m for m in MODES_UNDER_TEST if m in ("pdx8", "sketchpdx8"))


def _cfg_ee(method, theta, quant, early_exit, wave=64):
    return dataclasses.replace(
        _cfg(method, theta, quant, wave=wave),
        traversal=dataclasses.replace(TC, early_exit=early_exit))


@pytest.mark.parametrize("quant", PDX_MODES)
@pytest.mark.parametrize("method", ["nlj", "index", "es_mi"])
def test_early_exit_on_off_identical(golden_ds, golden_engine, golden_theta,
                                     method, quant):
    on = golden_engine.join(golden_ds.X,
                            _cfg_ee(method, golden_theta, quant, True))
    off = golden_engine.join(golden_ds.X,
                             _cfg_ee(method, golden_theta, quant, False))
    assert on.pair_set() == off.pair_set(), \
        (method, quant, len(on.pair_set() ^ off.pair_set()))
    assert on.stats.n_rerank == off.stats.n_rerank, (method, quant)


@pytest.mark.parametrize("quant", PDX_MODES)
def test_early_exit_streaming_submit_identical(golden_ds, golden_theta,
                                               quant):
    """The submit() leg: batch boundaries and the work-sharing carry do
    not break on/off equivalence."""
    sets = {}
    for ee in (True, False):
        eng = JoinEngine(golden_ds.Y, build_kw=BK)
        cfg = _cfg_ee("es_sws", golden_theta, quant, ee, wave=32)
        got = set()
        for b0 in range(0, golden_ds.X.shape[0], 40):
            got |= eng.submit(golden_ds.X[b0:b0 + 40], cfg).pair_set()
        sets[ee] = got
    assert sets[True] == sets[False], (quant,
                                       len(sets[True] ^ sets[False]))


@pytest.mark.skipif("pdx8" not in MODES_UNDER_TEST,
                    reason="pdx8 not in this matrix leg")
def test_early_exit_actually_skips_dims():
    """Regression floor for the point of the tier: on clustered data most
    NLJ lanes retire before the last slab (dims_scanned_frac < 1), while
    the full-scan run reports exactly 1 — and both emit the same pairs."""
    ds = make_dataset("clustered", n_data=1200, n_query=64, dim=96, seed=7)
    theta = float(thresholds(ds, 3)[0])
    eng = JoinEngine(ds.Y, build_kw=BK)
    on = eng.join(ds.X, _cfg_ee("nlj", theta, "pdx8", True))
    off = eng.join(ds.X, _cfg_ee("nlj", theta, "pdx8", False))
    assert on.pair_set() == off.pair_set()
    assert on.stats.n_dims_total == ds.X.shape[0] * ds.Y.shape[0] * 96
    assert on.stats.dims_scanned_frac < 1.0, on.stats.dims_scanned_frac
    assert off.stats.dims_scanned_frac == 1.0


# -- streaming regressions --------------------------------------------------


@pytest.mark.parametrize("quant", MODES_UNDER_TEST)
@pytest.mark.parametrize("method", ["nlj", "es"])
def test_streaming_matches_oneshot(golden_ds, golden_theta, method, quant):
    """submit() batches == one-shot join() pair set for batch-invariant
    methods (``nlj`` is exact; ``es`` lanes are independent, so batch
    boundaries cannot change results)."""
    eng = JoinEngine(golden_ds.Y, build_kw=BK)
    cfg = _cfg(method, golden_theta, quant, wave=32)
    one = eng.join(golden_ds.X, cfg).pair_set()
    got = set()
    for b0 in range(0, golden_ds.X.shape[0], 40):
        r = eng.submit(golden_ds.X[b0:b0 + 40], cfg)
        got |= r.pair_set()
    assert got == one, (method, quant, len(got ^ one))


@pytest.mark.parametrize("quant", MODES_UNDER_TEST)
def test_reset_stream_clears_carry_state(golden_ds, golden_theta, quant):
    """reset_stream() drops the work-sharing carry (and any quantized
    query state with it): resubmitting the same batches reproduces the
    first run exactly, under global ids restarting at 0."""
    eng = JoinEngine(golden_ds.Y, build_kw=BK)
    cfg = _cfg("es_sws", golden_theta, quant, wave=32)

    def run_stream():
        parts = []
        for b0 in range(0, golden_ds.X.shape[0], 40):
            parts.append(eng.submit(golden_ds.X[b0:b0 + 40], cfg).pairs)
        return np.concatenate(parts, axis=0)

    first = run_stream()
    assert eng.n_submitted == golden_ds.X.shape[0]
    assert len(eng._stream_cache) > 0, "es_sws must populate the carry"
    eng.reset_stream()
    assert eng.n_submitted == 0
    assert not eng._stream_cache and eng._carry_vecs is None
    second = run_stream()
    assert sorted(map(tuple, first.tolist())) == \
        sorted(map(tuple, second.tolist()))
