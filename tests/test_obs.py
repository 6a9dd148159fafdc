"""Observability suite — program spans on the profiler's clock, the
traversal's in-loop counters, and the metrics registry (obs/trace,
obs/metrics, core/traversal).

Four contracts:

  * **Observation never perturbs** — runs under a profiler session and
    runs without one emit *identical* pair sets and identical counters,
    across quant modes × overlap on/off.
  * **Spans are profiler annotations** — under a CPU profiler session
    the program's span names appear bare in ``ProfileData`` (the MI
    path, the ``es_sws`` search path, a service round), every ``wave/*``
    span lies inside a ``join`` span, every ``wave/fetch`` inside its
    parent, and ``launch/join.py --trace DIR`` writes an ``.xplane.pb``
    that holds them.
  * **The counters count the work done** — ``n_slots`` is every probed
    candidate slot (B·K per probe, masked ones included) and
    ``n_lane_iters`` each valid lane's active iterations, checked against
    the loop shapes and against each lane traversed alone.
  * **The registry is the single backend** — ``JoinStats.merge`` is an
    associative, field-complete combine (hypothesis); ``publish`` /
    ``from_metrics`` roundtrip through a ``Metrics`` registry;
    ``JoinEngine.cumulative_stats`` equals the merge of per-batch stats;
    cache hit/miss/eviction counters move under the streaming
    work-sharing paths.

CI runs this module in the quant-mode matrix (``REPRO_QUANT_MODE``
narrows the golden parametrization).
"""
import dataclasses
import glob
import json
import os
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import JoinConfig, TraversalConfig
from repro.core.types import JoinStats
from repro.data.vectors import make_dataset, thresholds
from repro.engine import JoinEngine
from repro.engine import waves as W
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

_ENV_MODE = os.environ.get("REPRO_QUANT_MODE")
GOLDEN_MODES = (_ENV_MODE,) if _ENV_MODE else ("off", "sq8", "pdx8")
ROOT = Path(__file__).resolve().parents[1]

BK = dict(k=24, degree=12)
# wall-clock fields: the only ones a profiler session may move
_CLOCKS = {"greedy_seconds", "expand_seconds", "other_seconds",
           "wait_seconds", "total_seconds"}


def _tc(**kw):
    base = dict(beam_width=64, expand_per_iter=4, pool_cap=1024,
                hybrid_beam=64, seeds_max=8, max_iters=2048)
    base.update(kw)
    return TraversalConfig(**base)


def _cfg(method, theta, quant="off", *, overlap=True, wave=32, tc=None):
    return JoinConfig(method=method, theta=theta, traversal=tc or _tc(),
                      wave_size=wave, quant=quant, overlap=overlap)


def _counters(stats: JoinStats) -> dict:
    return {k: v for k, v in stats.as_dict().items() if k not in _CLOCKS}


@pytest.fixture(scope="module")
def ds():
    return make_dataset("manifold", n_data=1500, n_query=96, dim=40,
                        seed=42)


@pytest.fixture(scope="module")
def theta(ds):
    return float(thresholds(ds, 3)[1])


class Spans(list):
    """Host spans of one profiler trace: (name, start_ns, end_ns, stats)."""

    def named(self, name):
        return [sp for sp in self if sp[0] == name]


def _profile_spans(log_dir) -> Spans:
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = Spans()
    with warnings.catch_warnings():
        # reading jaxlib's event stats warns of their type's missing module
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def _traced(log_dir, fn):
    with obs_trace.profile(str(log_dir)):
        assert obs_trace.recording()
        res = fn()
    return res, _profile_spans(log_dir)


# -- observation never perturbs ----------------------------------------------


@pytest.mark.parametrize("quant", GOLDEN_MODES)
@pytest.mark.parametrize("overlap", [True, False])
def test_traced_matches_untraced(ds, theta, quant, overlap, tmp_path):
    """Golden equivalence: tracing is observation, never scheduling —
    same engine, same config, identical pair sets and counters with the
    profiler off vs on."""
    eng = JoinEngine(ds.Y, build_kw=BK, metrics=obs_metrics.Metrics())
    cfg = _cfg("es_mi", theta, quant, overlap=overlap)
    r_plain = eng.join(ds.X, cfg)
    r_traced, spans = _traced(tmp_path, lambda: eng.join(ds.X, cfg))
    assert r_traced.pair_set() == r_plain.pair_set(), (quant, overlap)
    assert _counters(r_traced.stats) == _counters(r_plain.stats)
    assert spans.named("wave/assemble")


def test_traced_matches_untraced_search_path(ds, theta, tmp_path):
    """Same contract on the work-sharing search path (hit/miss counters
    and the cache-update span live there)."""
    eng = JoinEngine(ds.Y, build_kw=BK, metrics=obs_metrics.Metrics())
    cfg = _cfg("es_hws", theta)
    r_plain = eng.join(ds.X, cfg)
    r_traced, spans = _traced(tmp_path, lambda: eng.join(ds.X, cfg))
    assert r_traced.pair_set() == r_plain.pair_set()
    assert _counters(r_traced.stats) == _counters(r_plain.stats)
    assert r_plain.stats.cache_hits > 0
    assert spans.named("wave/cache_update")


def test_spans_open_without_a_session_and_calls_nest():
    """Spans always open; without a profiler session ``recording()`` is
    false, so call sites compute no attributes. Each call gets a fresh
    id, and a nested call its own."""
    assert not obs_trace.recording()
    with obs_trace.span("wave/assemble", wave=3) as sp:
        sp.set_metadata(pairs=1)
    with obs_trace.call_span("join") as call:
        assert call > 0
        with obs_trace.call_span("join") as inner:
            assert inner > call


# -- spans are profiler annotations ------------------------------------------


def _serve_round(ds, theta):
    from repro.serve import JoinRequest, JoinService, ServiceConfig
    svc = JoinService(ServiceConfig(buckets=(32,)),
                      metrics=obs_metrics.Metrics())
    svc.load("t", ds.Y, build_kw=BK, default=_cfg("es_sws", theta))
    svc.warmup("t", thetas=[theta])
    for uid in range(2):
        svc.submit(JoinRequest(uid=uid, tenant="t",
                               X=np.asarray(ds.X[uid * 20:uid * 20 + 20]),
                               theta=theta, method="es_sws"))
    return svc.run()


# what each traced run must show, by path
TRACED = {
    "es_mi_adapt": {"join", "join/prepare", "join/ood", "index/build",
                    "wave/launch", "wave/band", "wave/fetch",
                    "wave/assemble"},
    "es_sws": {"join", "join/prepare", "index/build", "wave/launch",
               "wave/band", "wave/feedback", "wave/fetch", "wave/assemble",
               "wave/cache_update"},
    "serve": {"serve_join/warmup", "serve_join/round",
              "serve_join/tenant_batch", "join", "join/prepare",
              "wave/launch", "wave/band", "wave/feedback", "wave/fetch",
              "wave/assemble", "wave/cache_update"},
}


@pytest.fixture(scope="module")
def traced_runs(ds, theta, tmp_path_factory):
    """One traced run per path, from a cold engine (so the index builds
    fall inside the session)."""
    out = {}
    for path in TRACED:
        log = tmp_path_factory.mktemp(f"prof-{path}")
        if path == "serve":
            fn = lambda: _serve_round(ds, theta)        # noqa: E731
        else:
            def fn(m=path):
                eng = JoinEngine(ds.Y, build_kw=BK,
                                 metrics=obs_metrics.Metrics())
                return eng.join(ds.X, _cfg(m, theta))
        out[path] = _traced(log, fn)
    return out


@pytest.mark.parametrize("path", sorted(TRACED))
def test_span_names_appear_bare(traced_runs, path):
    res, spans = traced_runs[path]
    names = {sp[0] for sp in spans}
    assert TRACED[path] <= names, TRACED[path] - names
    # the retired device lane and its instants are gone
    assert not names & {"wave/device", "wave/overflow_retry",
                        "serve_join/reject"}
    # attributes ride as event stats, never in the name
    waves = [sp for sp in spans if sp[0].startswith("wave/")]
    assert all("wave" in sp[3] and "call" in sp[3] for sp in waves)


@pytest.mark.parametrize("path", sorted(TRACED))
def test_wave_spans_nest_in_join_and_fetch_in_parent(traced_runs, path):
    _, spans = traced_runs[path]
    joins = spans.named("join")
    parents = [sp for sp in spans
               if sp[0] in ("wave/band", "wave/feedback", "wave/assemble")]
    for name, t0, t1, st in spans:
        if not name.startswith("wave/"):
            continue
        outer = [j for j in joins if j[1] <= t0 and t1 <= j[2]]
        assert outer, name
        # the wave's spans carry the call id of the join around them
        assert st["call"] in {j[3]["call"] for j in outer}, (name, st)
        if name == "wave/fetch":
            assert any(p[1] <= t0 and t1 <= p[2] and p[3]["wave"] ==
                       st["wave"] for p in parents), st


def test_launch_join_trace_dir_writes_xplane(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.join", "--method",
         "es_mi_adapt", "--n-data", "800", "--n-query", "48", "--dim",
         "16", "--wave", "16", "--engine-spec", "ci", "--trace",
         str(tmp_path / "prof")],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "sound=True" in p.stdout, p.stdout
    spans = _profile_spans(tmp_path / "prof")
    names = {sp[0] for sp in spans}
    assert {"join", "index/build", "wave/launch", "wave/assemble",
            "wave/fetch"} <= names, names
    assert glob.glob(str(tmp_path / "prof/plugins/profile/*/*.trace.json.gz"))


# -- the counters count the work done ----------------------------------------


@pytest.fixture(scope="module")
def eng(ds):
    return JoinEngine(ds.Y, build_kw=BK, metrics=obs_metrics.Metrics())


def _one_wave(eng, ds, theta, path, lanes, B):
    """Launch and assemble one wave of ``lanes`` real queries padded to
    ``B``; returns (stats, per-lane active iterations, the config)."""
    cfg = _cfg("es_mi" if path == "mi" else "es", theta, wave=B)
    qids, lane_valid = W.pad_wave(np.asarray(lanes, np.int32), B)
    xw = jnp.asarray(np.asarray(ds.X)[qids])
    stats = JoinStats()
    if path == "mi":
        h = W.launch_mi_wave(eng.merged_index(ds.X), xw, qids, lane_valid,
                             cfg, stats, hybrid=False)
    else:
        iy = eng.index_y()
        S = cfg.traversal.seeds_max
        seeds, sv = W.seeds_from_cache(qids, lane_valid, {}, {},
                                       int(iy.start), B, S)
        h = W.launch_search_wave(iy, xw, qids, lane_valid, cfg, stats,
                                 seeds=seeds, seeds_valid=sv)
    W.assemble_wave(h, stats)
    return stats, np.asarray(h.n_lane_iters), cfg


@pytest.mark.parametrize("path", ["mi", "search"])
def test_n_slots_counts_every_probed_slot(eng, ds, theta, path):
    """B·K per probe, masked slots included: the initial probe (the
    merged index's neighbour row, B·R, or the search's seeds, B·S) and
    B·E·R per loop iteration."""
    B = 8
    stats, _, cfg = _one_wave(eng, ds, theta, path, range(6), B)
    E = cfg.traversal.expand_per_iter
    if path == "mi":
        R = eng.merged_index(ds.X).nbrs.shape[1]
        first = B * R
    else:
        R = eng.index_y().nbrs.shape[1]
        first = B * cfg.traversal.seeds_max
    assert stats.n_iters > 0
    assert stats.n_slots == first + stats.n_iters * B * E * R
    assert 0 < stats.n_dist < stats.n_slots


@pytest.mark.parametrize("path", ["mi", "search"])
def test_n_lane_iters_matches_each_lane_alone(eng, ds, theta, path):
    """A lane's active iterations in a wave equal the iterations its
    traversal takes alone (a one-lane wave runs until that lane is
    done); padding lanes are left out of the sum."""
    lanes = [3, 17, 40, 41, 77, 90]
    B = 8
    stats, per_lane, _ = _one_wave(eng, ds, theta, path, lanes, B)
    ref = [_one_wave(eng, ds, theta, path, [q], 1)[0].n_iters
           for q in lanes]
    assert per_lane[:len(lanes)].tolist() == ref
    assert stats.n_lane_iters == sum(ref)
    assert stats.n_lane_iters <= B * stats.n_iters


_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    from repro.core import TraversalConfig
    from repro.core.distributed import (build_sharded_merged_index,
                                        distributed_mi_join)
    from repro.data.vectors import make_dataset, thresholds

    ds = make_dataset("manifold", n_data=600, n_query=40, dim=16, seed=5)
    theta = float(thresholds(ds, 3)[1])
    mesh = jax.make_mesh((2,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    smi = build_sharded_merged_index(ds.Y, ds.X, 2, k=16, degree=8)
    tc = TraversalConfig(beam_width=32, expand_per_iter=4, pool_cap=256,
                         hybrid_beam=32, seeds_max=8, max_iters=512)
    B, S = 16, 2
    _, st = distributed_mi_join(ds.X, smi, mesh, "data", theta=theta,
                                cfg=tc, wave_size=B)
    R = smi.nbrs.shape[-1]
    waves = -(-40 // B)
    assert st.n_iters > 0
    assert st.n_slots == waves * S * B * R + st.n_iters * B * 4 * R, st
    assert 0 < st.n_lane_iters <= B * st.n_iters, st
    assert st.n_dist < st.n_slots
    print("SHARDED_COUNTERS_OK")
""")


def test_sharded_path_counts_slots_and_lane_iters():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=ROOT)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "SHARDED_COUNTERS_OK" in p.stdout


# -- metrics registry --------------------------------------------------------


def test_counter_monotonic():
    m = obs_metrics.Metrics()
    c = m.counter("a", help="h")
    c.inc()
    c.inc(2)
    assert m.value("a") == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    assert m.counter("a") is c          # get-or-create


def test_gauge_set_max():
    m = obs_metrics.Metrics()
    g = m.gauge("g")
    g.set(5.0)
    g.set_max(3.0)
    assert m.value("g") == 5.0
    g.set_max(9.0)
    assert m.value("g") == 9.0
    g.set(1.0)                          # plain set may decrease
    assert m.value("g") == 1.0


def test_histogram_buckets():
    m = obs_metrics.Metrics()
    h = m.histogram("h", buckets=(1.0, 4.0, 16.0))
    for v in (0.5, 2, 3, 100):
        h.observe(v)
    assert h.counts == [1, 2, 0, 1]     # last slot is the +Inf tail
    assert h.cumulative() == [1, 3, 3, 4]
    assert h.count == 4 and h.sum == pytest.approx(105.5)
    assert m.value("h") == 4            # scalar view of a histogram
    with pytest.raises(ValueError):
        m.histogram("bad", buckets=(4.0, 1.0))
    with pytest.raises(ValueError):
        m.histogram("empty", buckets=())


def test_kind_mismatch_raises():
    m = obs_metrics.Metrics()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")
    with pytest.raises(TypeError):
        m.histogram("x")


def test_prometheus_text_format():
    m = obs_metrics.Metrics()
    m.counter("join.n_dist", help="distances").inc(7)
    m.gauge("9lives").set(2)
    m.histogram("wave.occ", buckets=(2.0,)).observe(1)
    text = m.prometheus_text()
    assert "# HELP join_n_dist distances" in text
    assert "# TYPE join_n_dist counter" in text
    assert "join_n_dist 7" in text
    assert "_9lives 2" in text          # leading digit sanitized
    assert 'wave_occ_bucket{le="2"} 1' in text
    assert 'wave_occ_bucket{le="+Inf"} 1' in text
    assert "wave_occ_count 1" in text
    assert text.endswith("\n")


def test_snapshot_and_clear():
    m = obs_metrics.Metrics()
    m.counter("c").inc(2)
    m.gauge("g").set(1)
    m.histogram("h").observe(3)
    snap = m.snapshot()
    assert snap["counters"] == {"c": 2}
    assert snap["gauges"] == {"g": 1}
    assert snap["histograms"]["h"]["count"] == 1
    json.dumps(snap)                    # export-safe
    m.clear()
    assert m.names() == [] and m.value("c", default=-1) == -1


# -- JoinStats: merge / publish / from_metrics -------------------------------


def test_every_stats_field_is_classified():
    """Merge is field-driven: every dataclass field is additive unless
    registered in exactly one of the non-additive classes, so a newly
    added counter is merge-covered by default."""
    names = {f.name for f in dataclasses.fields(JoinStats)}
    assert set(JoinStats._MERGE_MAX) <= names
    assert set(JoinStats._MERGE_CAT) <= names
    assert not set(JoinStats._MERGE_MAX) & set(JoinStats._MERGE_CAT)
    # and the default-additive remainder actually supports +
    JoinStats().merge(JoinStats())


def test_merge_semantics():
    a = JoinStats(n_dist=3, peak_cache_entries=5, band_occ_per_shard=(1, 2),
                  greedy_seconds=0.5, cache_hits=1)
    b = JoinStats(n_dist=4, peak_cache_entries=2, band_occ_per_shard=(7,),
                  greedy_seconds=0.25, cache_hits=2)
    m = a.merge(b)
    assert m.n_dist == 7
    assert m.peak_cache_entries == 5            # high-water mark
    assert m.band_occ_per_shard == (1, 2, 7)    # shard groups concatenate
    assert m.greedy_seconds == 0.75
    assert m.cache_hits == 3


def test_publish_from_metrics_roundtrip():
    m = obs_metrics.Metrics()
    s = JoinStats(n_dist=7, greedy_seconds=0.5, peak_cache_entries=3,
                  band_occ_per_shard=(4, 9), cache_hits=2, cache_misses=1,
                  bytes_band=128, wait_seconds=0.25)
    s.publish(m)
    assert JoinStats.from_metrics(m) == s
    # second publish: counters accumulate, peaks max, shard gauges are
    # last-write (per-join listings, not sums)
    s.publish(m)
    back = JoinStats.from_metrics(m)
    assert back.n_dist == 14 and back.peak_cache_entries == 3
    assert back.band_occ_per_shard == (4, 9)
    assert m.value("join.shard_band_imbalance") == pytest.approx(9 / 6.5)


try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYP = True
except ImportError:                                    # pragma: no cover
    _HAVE_HYP = False

if not _HAVE_HYP:                                      # pragma: no cover

    @pytest.mark.skip(reason="property tests need the hypothesis dev extra")
    def test_merge_associativity():
        pass

if _HAVE_HYP:

    def _rand_stats(data):
        kw = {}
        for f in dataclasses.fields(JoinStats):
            if f.name in JoinStats._MERGE_CAT:
                kw[f.name] = tuple(data.draw(
                    st.lists(st.integers(0, 50), max_size=3)))
            elif f.type == "float":
                # dyadic rationals: float sums stay exact, so associativity
                # is an equality, not an approximation
                kw[f.name] = data.draw(st.integers(0, 1 << 12)) / 8.0
            else:
                kw[f.name] = data.draw(st.integers(0, 10_000))
        return JoinStats(**kw)

    @settings(deadline=None, max_examples=50)
    @given(st.data())
    def test_merge_associativity(data):
        """(a ⊕ b) ⊕ c == a ⊕ (b ⊕ c) over every field class — the
        property that makes per-shard / per-batch reduction order
        irrelevant."""
        a, b, c = (_rand_stats(data) for _ in range(3))
        assert a.merge(b).merge(c) == a.merge(b.merge(c))
        # identity element
        assert a.merge(JoinStats()) == a


# -- engine surfaces ---------------------------------------------------------


@pytest.mark.parametrize("carry_window", [4096, 16])
def test_streaming_cache_counters(ds, theta, carry_window):
    """The work-sharing cache counters move under streaming submit, and
    the engine-lifetime aggregate equals the merge of per-batch stats."""
    eng = JoinEngine(ds.Y, build_kw=BK, carry_window=carry_window,
                     metrics=obs_metrics.Metrics())
    cfg = _cfg("es_sws", theta)
    tot = JoinStats()
    for b0 in range(0, ds.X.shape[0], 40):
        tot = tot.merge(eng.submit(ds.X[b0:b0 + 40], cfg).stats)
    assert tot.cache_hits + tot.cache_misses > 0
    if carry_window == 16:
        # window smaller than the stream: donors must have been evicted
        assert tot.cache_evictions > 0
    cum = eng.cumulative_stats()
    assert cum.n_dist == tot.n_dist
    assert cum.cache_hits == tot.cache_hits
    assert cum.cache_evictions == tot.cache_evictions
    assert cum.cache_tombstones == tot.cache_tombstones


def test_engine_cache_event_and_serve_counters(ds, theta):
    m = obs_metrics.Metrics()
    eng = JoinEngine(ds.Y, build_kw=BK, metrics=m)
    cfg = _cfg("es_hws", theta)
    eng.join(ds.X, cfg)
    assert m.value("engine.cache.index_y.miss") >= 1
    eng.join(ds.X, cfg)
    assert m.value("engine.cache.index_y.hit") >= 1
    assert m.value("engine.joins") == 2
    assert m.value("engine.queries") == 2 * ds.X.shape[0]
    snap = eng.metrics_snapshot()
    assert "engine.joins" in snap["counters"]
    assert any(k.startswith("join.") for k in snap["counters"])


def test_ambient_wave_histograms(ds, theta):
    """Wave-level histograms land on the process-global registry even
    when the engine uses a private one (ambient instrumentation)."""
    g = obs_metrics.metrics()
    before = g.value("wave.pairs", 0)
    eng = JoinEngine(ds.Y, build_kw=BK, metrics=obs_metrics.Metrics())
    eng.join(ds.X, _cfg("es_mi", theta))
    assert g.value("wave.pairs", 0) > before
    assert g.get("wave.band_occ") is not None
