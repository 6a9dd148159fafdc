"""N-device mesh driver: MeshPlan decision rule + equivalence matrix.

The in-process tests cover the ``MeshPlan`` planner (pure logic, device
count passed explicitly). The ``@slow`` tests are subprocesses forcing 8
host devices (jax locks the device count at first init, so the suite's
own process stays single-device): the 2/4/8-shard × quant-mode matrix on
an uneven N_y asserts pair sets and work-sharing cache counters
identical to single-device, the hybrid leg asserts the dimension-
partitioned ``psum`` partials are bitwise-equal to the unsharded slab
sums on CPU (the admissibility contract behind certified early exit),
and the combine legs assert ``all_gather`` and ``ppermute`` ring pool
merges emit identical pairs on both the NLJ and MI drivers (with the
requested collective really present in the traced MI step).
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.distributed import (DEFAULT_MERGE_CAP, HYBRID_ROW_FLOOR,
                                    MeshPlan, POOL_COMBINE_RING_MIN)


# -- MeshPlan planner (pure logic) ------------------------------------------


def test_meshplan_vector_for_traversal():
    """Traversal methods hop the graph with whole vectors resident: the
    planner never splits dims for them, whatever the shape."""
    for shards in (2, 4, 8):
        p = MeshPlan.plan(100, 4096, shards, devices=8, traversal=True)
        assert p.kind == "vector" and p.dim_shards == 1
        assert p.n_shards == shards and p.n_devices == shards


def test_meshplan_hybrid_for_small_rows_large_dims():
    """NLJ with few rows per shard and ≥ 1 whole PDX slab per dim group
    moves power-of-two factors onto the model axis."""
    p = MeshPlan.plan(1_000, 128, 4, devices=8, traversal=False)
    assert p.kind == "hybrid"
    assert (p.n_shards, p.dim_shards) == (2, 2)
    assert p.n_devices == 4
    # rows/shard already ≥ floor: stay pure vector
    p = MeshPlan.plan(HYBRID_ROW_FLOOR * 8, 128, 4, devices=8,
                      traversal=False)
    assert p.kind == "vector" and p.dim_shards == 1
    # dims too small to give every model rank a whole slab: pure vector
    p = MeshPlan.plan(1_000, 64, 4, devices=8, traversal=False)
    assert p.kind == "vector" and p.dim_shards == 1


def test_meshplan_pool_combine_routing():
    """all_gather for small shard groups, ppermute ring from
    POOL_COMBINE_RING_MIN data shards up; explicit override wins."""
    small = MeshPlan.plan(10 ** 6, 40, POOL_COMBINE_RING_MIN - 1,
                          devices=16, traversal=False)
    assert small.pool_combine == "all_gather"
    big = MeshPlan.plan(10 ** 6, 40, POOL_COMBINE_RING_MIN,
                        devices=16, traversal=False)
    assert big.pool_combine == "ppermute"
    forced = MeshPlan.plan(10 ** 6, 40, 2, devices=16, traversal=False,
                           pool_combine="ppermute")
    assert forced.pool_combine == "ppermute"


def test_meshplan_auto_uses_all_devices():
    for auto in (0, "auto", None):
        p = MeshPlan.plan(10 ** 6, 40, auto, devices=8, traversal=True)
        assert p.n_shards == 8


def test_meshplan_too_many_shards_is_a_clear_error():
    with pytest.raises(ValueError, match="device"):
        MeshPlan.plan(10 ** 6, 40, 16, devices=8, traversal=True)
    with pytest.raises(ValueError):
        MeshPlan.plan(10 ** 6, 40, -1, devices=8, traversal=True)


def test_engine_rejects_oversubscribed_shards():
    """The engine surfaces the planner's error before any shard_map."""
    import numpy as np

    from repro.engine import JoinEngine
    from repro.core.types import JoinConfig

    eng = JoinEngine(np.zeros((64, 8), np.float32), n_shards=16)
    with pytest.raises(ValueError, match="device"):
        eng.join(np.zeros((4, 8), np.float32),
                 JoinConfig(method="nlj", theta=1.0))


# -- forced-8-device equivalence matrix (subprocess) ------------------------


def _run_forced(script: str, marker: str, timeout: int = 1200) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    assert marker in r.stdout, r.stdout + r.stderr


_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.core import (JoinConfig, TraversalConfig, exact_join_pairs,
                            theta_band)
    from repro.data.vectors import make_dataset, thresholds
    from repro.engine import JoinEngine

    # uneven N_y: 1501 is not divisible by 2, 4, or 8, so every shard
    # count exercises the sentinel-padding path
    ds = make_dataset("manifold", n_data=1501, n_query=64, dim=40, seed=42)
    theta = float(thresholds(ds, 3)[0])
    truth = set(map(tuple, exact_join_pairs(ds.X, ds.Y, theta).tolist()))
    # pairs within f32 rounding of θ: matmul-form and difference-form
    # evaluations may round them to either side
    band = theta_band(ds.X, ds.Y, theta)

    def agree(a, b):
        return not ((a ^ b) - band)

    tc = TraversalConfig(beam_width=64, expand_per_iter=4, pool_cap=1024,
                         hybrid_beam=64, seeds_max=8, max_iters=2048)
    BK = dict(k=24, degree=12)

    CACHE_FIELDS = ("peak_cache_entries", "cache_hits", "cache_misses",
                    "cache_evictions", "cache_tombstones")
""")

_MATRIX_SCRIPT = _PRELUDE + textwrap.dedent("""
    for quant in ("off", "sq8", "pdx8"):
        cfg = JoinConfig(method="es_mi", theta=theta, traversal=tc,
                         wave_size=32, quant=quant)
        ref = JoinEngine(ds.Y, build_kw=BK, n_shards=1).join(ds.X, cfg)
        assert agree(ref.pair_set(), truth), (quant, "single != truth")
        for s in (2, 4, 8):
            e = JoinEngine(ds.Y, build_kw=BK, n_shards=s)
            r = e.join(ds.X, cfg)
            assert r.pair_set() == ref.pair_set(), (
                quant, s, len(r.pair_set() ^ ref.pair_set()))
            for f in CACHE_FIELDS:
                assert getattr(r.stats, f) == getattr(ref.stats, f), (
                    quant, s, f)
            assert len(r.stats.band_occ_per_shard) == s
    # exact NLJ through the mesh driver, same uneven N_y
    cfgn = JoinConfig(method="nlj", theta=theta, traversal=tc, wave_size=32)
    for s in (2, 4, 8):
        rn = JoinEngine(ds.Y, build_kw=BK, n_shards=s).join(ds.X, cfgn)
        assert agree(rn.pair_set(), truth), (s, len(rn.pair_set() ^ truth))
    print("MESH_MATRIX_OK")
""")


@pytest.mark.slow
def test_mesh_equivalence_matrix_8dev():
    """2/4/8 shards × off/sq8/pdx8 on uneven N_y: pair sets and work-
    sharing cache counters identical to single-device; exact NLJ matches
    ground truth at every shard count."""
    _run_forced(_MATRIX_SCRIPT, "MESH_MATRIX_OK")


_STREAM_SCRIPT = _PRELUDE + textwrap.dedent("""
    for method in ("es_mi", "nlj"):
        cfg = JoinConfig(method=method, theta=theta, traversal=tc,
                         wave_size=32)
        ref = JoinEngine(ds.Y, build_kw=BK, n_shards=1)
        got_ref, got = set(), set()
        e = JoinEngine(ds.Y, build_kw=BK, n_shards=4)
        for b0 in range(0, 64, 16):
            got_ref |= ref.submit(ds.X[b0:b0 + 16], cfg).pair_set()
            got |= e.submit(ds.X[b0:b0 + 16], cfg).pair_set()
        if method == "nlj":
            # one (B, N_y) matmul against four (B, N_y/4) ones: XLA blocks
            # the reduction by shape, so a pair within f32 rounding of θ
            # may fall to either side ((18, 733), 4.6e-8 above θ, does)
            assert agree(got, got_ref), (method, got ^ got_ref)
        else:
            assert got == got_ref, (method, len(got ^ got_ref))
        assert agree(got, truth), (method, len(got ^ truth))
        assert len(e._stream_cache) == len(ref._stream_cache)
        assert e.n_submitted == ref.n_submitted == 64
    print("MESH_STREAM_OK")
""")


@pytest.mark.slow
def test_mesh_streaming_submit_8dev():
    """Sharded submit() batches carry global query ids and the same
    stream state as single-device, for both the MI and NLJ routes."""
    _run_forced(_STREAM_SCRIPT, "MESH_STREAM_OK")


_HYBRID_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax.numpy as jnp
    from repro.core import distributed as D
    from repro.core import exact_join_pairs

    rng = np.random.default_rng(0)
    X = rng.normal(size=(96, 128)).astype(np.float32)
    Y = rng.normal(size=(771, 128)).astype(np.float32)
    theta = 14.9
    truth = set(map(tuple, exact_join_pairs(X, Y, theta).tolist()))

    plan = D.MeshPlan.plan(Y.shape[0], X.shape[1], 4, traversal=False)
    assert plan.kind == "hybrid" and plan.dim_shards == 2

    # admissibility contract: the psum of per-rank slab partials must be
    # bitwise-equal (CPU) to the unsharded per-group sums, or the
    # certified tail bound could mis-retire a lane
    mesh = plan.make_mesh()
    f = D.make_hybrid_sq_dists(mesh, plan)
    Xp, _ = D._pad_cols(X, plan.dim_shards, 64)
    Yp, _ = D._pad_cols(Y, plan.dim_shards, 64)
    d2_mesh = np.asarray(f(Xp, Yp))
    parts = D.slab_partial_sq_dists(X, Y, plan.dim_shards)
    d2_ref = np.asarray(jnp.sum(parts, axis=0))
    assert np.array_equal(d2_mesh, d2_ref), np.abs(d2_mesh - d2_ref).max()

    # hybrid and pure-vector plans emit the same exact pair set
    ph, _ = D.distributed_nlj_join(X, Y, plan, theta=theta, wave_size=32)
    assert set(map(tuple, ph.tolist())) == truth
    pv, sv = D.distributed_nlj_join(
        X, Y, D.MeshPlan(n_shards=4), theta=theta, wave_size=32)
    assert set(map(tuple, pv.tolist())) == truth

    # all_gather vs ppermute ring: identical pairs, only the collective
    # (and its byte meter) differs
    pr, sr = D.distributed_nlj_join(
        X, Y, D.MeshPlan(n_shards=8, pool_combine="ppermute"),
        theta=theta, wave_size=32)
    assert set(map(tuple, pr.tolist())) == truth
    assert sr.bytes_ppermute > 0 and sr.bytes_allgather == 0
    assert sv.bytes_allgather > 0 and sv.bytes_ppermute == 0
    print("MESH_HYBRID_OK")
""")


@pytest.mark.slow
def test_hybrid_partition_admissibility_8dev():
    """Dimension-partitioned psum partials are bitwise-equal to unsharded
    slab sums on CPU; hybrid, vector, and ring-combine plans all emit the
    exact pair set."""
    _run_forced(_HYBRID_SCRIPT, "MESH_HYBRID_OK")


_MI_RING_SCRIPT = _PRELUDE + textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    from repro.core import distributed as D

    smi = D.build_sharded_merged_index(ds.Y, ds.X, 8, **BK)
    kw = dict(theta=theta, cfg=tc, wave_size=32, n_data=1501)
    pa, sa = D.distributed_mi_join(
        ds.X, smi, plan=D.MeshPlan(n_shards=8, pool_combine="all_gather"),
        **kw)
    pp, sp = D.distributed_mi_join(
        ds.X, smi, plan=D.MeshPlan(n_shards=8, pool_combine="ppermute"),
        **kw)
    assert set(map(tuple, pp.tolist())) == set(map(tuple, pa.tolist()))
    assert agree(set(map(tuple, pp.tolist())), truth), (
        len(set(map(tuple, pp.tolist())) ^ truth))
    assert sp.bytes_ppermute > 0 and sp.bytes_allgather == 0
    assert sa.bytes_allgather > 0 and sa.bytes_ppermute == 0

    # regression: the requested collective must actually be in the
    # traced step — the ring used to silently lower to all_gather
    # because the single-name shard axis stayed a tuple, while the
    # driver kept metering bytes_ppermute
    for combine in ("ppermute", "all_gather"):
        plan = D.MeshPlan(n_shards=8, pool_combine=combine)
        mesh = plan.make_mesh()
        step, qargs = D.make_distributed_mi_join(
            mesh, plan.data_axis, smi, theta=theta, cfg=tc, n_data=1501,
            pool_combine=combine)
        B = 32
        with jax.set_mesh(mesh):
            jxp = str(jax.make_jaxpr(step)(
                smi.vecs, smi.nbrs, smi.mean_nbr_dist, smi.start, *qargs,
                jnp.asarray(ds.X[:B]), jnp.zeros((B,), jnp.int32),
                jnp.ones((B,), bool)))
        assert (combine == "ppermute") == ("ppermute" in jxp), combine
        assert (combine == "all_gather") == ("all_gather" in jxp), combine
    print("MESH_MI_RING_OK")
""")


@pytest.mark.slow
def test_mi_ring_combine_8dev():
    """The MI driver's ppermute ring pool merge emits the same pairs as
    all_gather, books bytes under the right meter, and the ring is
    really in the compiled step (not a silent all_gather fallback)."""
    _run_forced(_MI_RING_SCRIPT, "MESH_MI_RING_OK")


_SERVE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    from repro.configs.vectorjoin import preset
    from repro.core import exact_join_pairs, theta_band
    from repro.data.vectors import make_dataset, thresholds
    from repro.obs import metrics as obs_metrics
    from repro.serve import JoinRequest, JoinService, ServiceConfig

    ds = make_dataset("manifold", n_data=1501, n_query=64, dim=40, seed=42)
    theta = float(thresholds(ds, 3)[0])
    svc = JoinService(ServiceConfig(buckets=(32, 64), max_queue=64))
    svc.load("t0", ds.Y, default=preset("nlj", theta=theta),
             engine_kw=dict(n_shards=4))
    svc.warmup("t0", thetas=[theta], methods=("nlj",), quants=("off",))
    for uid in range(6):
        n = 11 + 7 * uid
        svc.submit(JoinRequest(uid=uid, tenant="t0", X=ds.X[:n],
                               theta=theta, method="nlj", quant="off"))
    c0 = obs_metrics.compile_count()
    done = svc.run()
    assert obs_metrics.compile_count() == c0, "sharded serve recompiled"
    truth = set(map(tuple, exact_join_pairs(ds.X, ds.Y, theta).tolist()))
    band = theta_band(ds.X, ds.Y, theta)
    for sj in done.values():
        assert sj.ok
        n = sj.n_queries
        t = {p for p in truth if p[0] < n}
        assert not ((sj.pair_set_local() ^ t) - band), (sj.uid, n)
    print("MESH_SERVE_OK")
""")


@pytest.mark.slow
def test_sharded_serving_flat_compiles_4dev():
    """A sharded nlj tenant serves mixed-size requests through the bucket
    ladder with zero steady-state recompiles and exact results."""
    _run_forced(_SERVE_SCRIPT, "MESH_SERVE_OK")
