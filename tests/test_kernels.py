"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes. Hypothesis property sweeps live in
tests/test_kernel_properties.py (they self-skip without the dev extra)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref

SHAPES_PAIRWISE = [
    (8, 128, 32), (16, 256, 64), (10, 130, 48),   # ragged → padding path
    (64, 512, 128), (8, 128, 960),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("B,N,d", SHAPES_PAIRWISE)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pairwise_pallas_matches_ref(B, N, d, dtype):
    rng = np.random.default_rng(B * N + d)
    x = jnp.asarray(rng.normal(size=(B, d)), dtype)
    y = jnp.asarray(rng.normal(size=(N, d)), dtype)
    got = ops.pairwise_sq_dists(x, y, impl="pallas_interpret")
    want = ref.pairwise_sq_dists(x, y)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol * d)


@pytest.mark.parametrize("B,K,d", [(8, 128, 32), (4, 96, 64), (16, 256, 200)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_rowwise_pallas_matches_ref(B, K, d, dtype):
    rng = np.random.default_rng(B * K + d)
    x = jnp.asarray(rng.normal(size=(B, d)), dtype)
    c = jnp.asarray(rng.normal(size=(B, K, d)), dtype)
    got = ops.rowwise_sq_dists(x, c, impl="pallas_interpret")
    want = ref.rowwise_sq_dists(x, c)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol * d)


@pytest.mark.parametrize("B,N,d,theta", [
    (8, 128, 32, 5.0), (16, 300, 64, 8.0), (10, 512, 128, 12.0)])
def test_nlj_count_pallas_matches_ref(B, N, d, theta):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    got = ops.nlj_count(x, y, theta=theta, impl="pallas_interpret")
    want = ref.nlj_count(x, y, theta)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_nlj_padding_never_matches():
    # padded y rows must not contaminate counts even with huge theta
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(3, 33)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(130, 33)), jnp.float32)
    got = ops.nlj_count(x, y, theta=1e6, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(got), np.full(3, 130))


# ---------------------------------------------------------------------------
# padding coverage: every user-facing shape must route through the kernels
# without tripping the block-divisibility asserts — including dimensions
# smaller than one block and empty inputs
# ---------------------------------------------------------------------------

AWKWARD_PAIRWISE = [
    (1, 1, 1), (9, 1, 1), (1, 700, 3), (40, 520, 640), (12, 96, 192),
    (0, 5, 4), (5, 0, 4), (2, 3, 0),
]


@pytest.mark.parametrize("B,N,d", AWKWARD_PAIRWISE)
def test_pairwise_padding_covers_all_shapes(B, N, d):
    rng = np.random.default_rng(B * 1000 + N * 10 + d)
    x = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    got = np.asarray(ops.pairwise_sq_dists(x, y, impl="pallas_interpret"))
    assert got.shape == (B, N)
    if B and N and d:
        assert_allclose(got, np.asarray(ref.pairwise_sq_dists(x, y)),
                        rtol=1e-5, atol=1e-4)
    else:
        assert_allclose(got, np.zeros((B, N), np.float32))


@pytest.mark.parametrize("B,K,d", [
    (1, 1, 1), (3, 5, 7), (12, 1, 520), (33, 257, 129),
    (0, 4, 8), (4, 0, 8), (2, 130, 0)])
def test_rowwise_padding_covers_all_shapes(B, K, d):
    rng = np.random.default_rng(B * 1000 + K * 10 + d)
    x = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(B, K, d)), jnp.float32)
    got = np.asarray(ops.rowwise_sq_dists(x, c, impl="pallas_interpret"))
    assert got.shape == (B, K)
    if B and K and d:
        assert_allclose(got, np.asarray(ref.rowwise_sq_dists(x, c)),
                        rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# int8 (QuantStore) kernels: interpret-mode Pallas vs dequantize-oracle,
# and certified bounds vs the true f32 distance
# ---------------------------------------------------------------------------

SHAPES_INT8 = [
    # (B, N, d, group_size) — ragged d / small groups / B below a sublane
    (8, 128, 32, 16), (10, 130, 48, 16), (3, 77, 200, 128), (16, 256, 64, 64),
]


def _store(rng, N, d, gs):
    from repro.quant import build_store
    Y = rng.normal(size=(N, d)).astype(np.float32)
    return Y, build_store(Y, group_size=gs)


@pytest.mark.parametrize("B,N,d,gs", SHAPES_INT8)
def test_pairwise_int8_pallas_matches_ref(B, N, d, gs):
    from repro.quant import quantize_queries
    rng = np.random.default_rng(B * N + d)
    Y, st = _store(rng, N, d, gs)
    qx, xn, _ = quantize_queries(rng.normal(size=(B, d)).astype(np.float32),
                                 st)
    want = np.asarray(ops.pairwise_sq_dists_int8(
        qx, st.q, st.scales, group_size=gs, impl="ref"))
    got = np.asarray(ops.pairwise_sq_dists_int8(
        qx, st.q, st.scales, group_size=gs, xn=xn, yn=st.norms,
        impl="pallas_interpret"))
    assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("B,K,d,gs", [
    (8, 128, 32, 16), (4, 96, 64, 64), (5, 33, 200, 128), (33, 7, 129, 128)])
def test_rowwise_int8_pallas_matches_ref(B, K, d, gs):
    from repro.quant import quantize_queries
    rng = np.random.default_rng(B * K + d)
    Y, st = _store(rng, max(K * 2, 64), d, gs)
    qx, _, _ = quantize_queries(rng.normal(size=(B, d)).astype(np.float32),
                                st)
    idx = rng.integers(0, Y.shape[0], (B, K))
    qc = jnp.asarray(np.asarray(st.q)[idx])
    want = np.asarray(ops.rowwise_sq_dists_int8(
        qx, qc, st.scales, group_size=gs, impl="ref"))
    got = np.asarray(ops.rowwise_sq_dists_int8(
        qx, qc, st.scales, group_size=gs, impl="pallas_interpret"))
    assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("B,N,d,gs", SHAPES_INT8)
def test_int8_bounds_bracket_f32_distance(B, N, d, gs):
    """The analytic error bound: quantized distance ± slack brackets the
    exact f32 distance for every pair."""
    from repro.quant import quantize_queries
    rng = np.random.default_rng(d * 7 + B)
    Y, st = _store(rng, N, d, gs)
    X = rng.normal(size=(B, d)).astype(np.float32)
    qx, xn, xe = quantize_queries(X, st)
    dhat = ops.pairwise_sq_dists_int8(
        qx, st.q, st.scales, group_size=gs, xn=xn, yn=st.norms,
        impl="pallas_interpret")
    slack = jnp.asarray(np.asarray(xe)[:, None]
                        + np.asarray(st.err)[None, :])
    true = np.asarray(ref.pairwise_sq_dists(jnp.asarray(X), jnp.asarray(Y)))
    lb = np.asarray(ops.quant_lower_bound(dhat, slack))
    ub = np.asarray(ops.quant_upper_bound(dhat, slack))
    tol = 1e-3 * max(d, 1)
    assert (lb <= true + tol).all()
    assert (ub >= true - tol).all()


# ---------------------------------------------------------------------------
# PDX (dimension-partitioned) kernels: interpret-mode Pallas vs the
# pure-jnp slab-scan oracle, swept over the slab-grid shapes that stress
# the padding path — d not a slab multiple, a single slab, d below one
# slab, and tiny slabs — with early exit both on and off
# ---------------------------------------------------------------------------

SHAPES_PDX = [
    # (B, N, d, slab) — slab-multiple / d∤slab / single-slab / d<slab /
    # tiny slab / ragged B,N below the block sizes / several lane-aligned
    # slabs (each slab its own block, no masking)
    (8, 128, 128, 64), (10, 130, 70, 64), (8, 96, 64, 64), (5, 77, 40, 64),
    (3, 50, 129, 16), (1, 1, 7, 64), (9, 130, 300, 128),
]


def _pdx(rng, B, N, d, slab):
    from repro.quant import build_pdx, pdx_queries
    Y = rng.normal(size=(N, d)).astype(np.float32)
    X = rng.normal(size=(B, d)).astype(np.float32)
    st = build_pdx(Y, slab=slab)
    return X, Y, st, pdx_queries(jnp.asarray(X), st)


@pytest.mark.parametrize("B,N,d,slab", SHAPES_PDX)
@pytest.mark.parametrize("early_exit", [False, True])
def test_pairwise_pdx_pallas_matches_ref(B, N, d, slab, early_exit):
    rng = np.random.default_rng(B * N + d + slab)
    X, Y, st, qc = _pdx(rng, B, N, d, slab)
    theta = 0.9 * np.sqrt(d)
    args = (qc.q, st.q, st.scales, qc.qslab, st.qslab, qc.qtail, st.qtail,
            qc.norms, st.norms, qc.err, st.err, jnp.float32(theta))
    want, wns = ops.pairwise_sq_dists_pdx(
        *args, slab=st.slab, dim=st.dim, early_exit=early_exit, impl="ref")
    got, gns = ops.pairwise_sq_dists_pdx(
        *args, slab=st.slab, dim=st.dim, early_exit=early_exit,
        impl="pallas_interpret")
    want, got = np.asarray(want), np.asarray(got)
    np.testing.assert_array_equal(np.asarray(wns), np.asarray(gns))
    np.testing.assert_array_equal(np.isinf(want), np.isinf(got))
    fin = np.isfinite(want)
    assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-3 * max(d, 1))
    if early_exit:
        # every retirement is certified: the true distance clears θ
        true = np.asarray(ref.pairwise_sq_dists(jnp.asarray(X),
                                                jnp.asarray(Y)))
        assert (true[~fin] >= theta ** 2).all()
    else:
        assert fin.all() and (np.asarray(gns) == st.n_slabs).all()


@pytest.mark.parametrize("B,N,d,slab", SHAPES_PDX)
@pytest.mark.parametrize("early_exit", [False, True])
def test_gather_pdx_pallas_matches_ref(B, N, d, slab, early_exit):
    rng = np.random.default_rng(B * 31 + N + d)
    X, Y, st, qc = _pdx(rng, B, N, d, slab)
    th2 = 0.8 ** 2 * d
    idx = rng.integers(0, N, (B, 9)).astype(np.int32)
    idx[rng.random((B, 9)) < 0.3] = -1      # NO_NODE slots
    args = (st.vp, st.ftail, st.ftail[:, 0], qc.vp, qc.ftail,
            qc.ftail[:, 0], jnp.asarray(idx), jnp.float32(th2))
    want, wns = ops.pdx_gather_sq_dists(
        *args, dim=st.dim, early_exit=early_exit, impl="ref")
    got, gns = ops.pdx_gather_sq_dists(
        *args, dim=st.dim, early_exit=early_exit, impl="pallas_interpret")
    want, got = np.asarray(want), np.asarray(got)
    np.testing.assert_array_equal(np.asarray(wns), np.asarray(gns))
    np.testing.assert_array_equal(np.isinf(want), np.isinf(got))
    fin = np.isfinite(want)
    assert_allclose(got[fin], want[fin], rtol=1e-5, atol=1e-4 * max(d, 1))
    # invalid slots retire immediately in both impls
    assert np.isinf(want[idx < 0]).all()
    assert (np.asarray(wns)[idx < 0] == 0).all()
    if early_exit:
        true = ((X[:, None].astype(np.float64)
                 - Y[np.maximum(idx, 0)].astype(np.float64)) ** 2
                ).sum(axis=2)
        retired = ~fin & (idx >= 0)
        assert (true[retired] >= th2).all()


def test_pdx_empty_and_degenerate_shapes():
    """Zero-row operands and an all-NO_NODE gather route through both
    impls without tripping the slab-grid padding asserts."""
    from repro.quant import build_pdx, pdx_queries
    rng = np.random.default_rng(7)
    Y = rng.normal(size=(20, 48)).astype(np.float32)
    st = build_pdx(Y, slab=64)
    q0 = pdx_queries(jnp.zeros((0, 48), jnp.float32), st)
    d0, n0 = ops.pairwise_sq_dists_pdx(
        q0.q, st.q, st.scales, q0.qslab, st.qslab, q0.qtail, st.qtail,
        q0.norms, st.norms, q0.err, st.err, jnp.float32(1.0),
        slab=st.slab, dim=st.dim, impl="pallas_interpret")
    assert d0.shape == (0, 20) and n0.shape == (0, 20)
    qc = pdx_queries(jnp.asarray(rng.normal(size=(3, 48)), jnp.float32), st)
    idx = jnp.full((3, 5), -1, jnp.int32)
    dist, ns = ops.pdx_gather_sq_dists(
        st.vp, st.ftail, st.ftail[:, 0], qc.vp, qc.ftail, qc.ftail[:, 0],
        idx, jnp.float32(4.0), dim=st.dim, early_exit=True,
        impl="pallas_interpret")
    assert np.isinf(np.asarray(dist)).all()
    assert (np.asarray(ns) == 0).all()
