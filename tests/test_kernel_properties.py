"""Hypothesis property sweeps for the distance / top-k-merge kernels.

Kept separate from tests/test_kernels.py and tests/test_new_kernels.py so
the deterministic Pallas-vs-reference validation there still runs in
environments without the ``dev`` extra; this module self-skips.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis dev extra")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref


@settings(deadline=None, max_examples=30)
@given(st.integers(2, 24), st.integers(1, 40), st.integers(1, 6),
       st.integers(0, 2**31 - 1))
def test_topk_merge_property(L, K, B, seed):
    """Merged beam == the L smallest of the union, ascending."""
    rng = np.random.default_rng(seed)
    bd = np.sort(rng.normal(size=(B, L)).astype(np.float32), axis=1)
    bi = rng.integers(0, 1000, (B, L)).astype(np.int32)
    cd = rng.normal(size=(B, K)).astype(np.float32)
    ci = rng.integers(0, 1000, (B, K)).astype(np.int32)
    md, mi = ops.topk_merge(jnp.asarray(bd), jnp.asarray(bi),
                            jnp.asarray(cd), jnp.asarray(ci))
    md = np.asarray(md)
    allv = np.concatenate([bd, cd], axis=1)
    want = np.sort(allv, axis=1)[:, :L]
    assert_allclose(md, want, rtol=1e-6)
    assert (np.diff(md, axis=1) >= 0).all()


@settings(deadline=None, max_examples=30)
@given(st.integers(2, 24), st.integers(1, 40), st.integers(1, 6),
       st.integers(0, 2**31 - 1))
def test_topk_merge_xla_equals_argsort_oracle(L, K, B, seed):
    """The default ``lax.top_k`` merge returns exactly the stable-argsort
    oracle's beam — distances and ids — with ties (coarse values) and
    +inf slots kept in position order."""
    rng = np.random.default_rng(seed)
    bd = np.sort(rng.integers(0, 6, (B, L)).astype(np.float32), axis=1)
    bd[:, L - int(rng.integers(0, L)):] = np.inf
    cd = rng.integers(0, 6, (B, K)).astype(np.float32)
    cd[rng.random((B, K)) < 0.2] = np.inf
    bi = rng.integers(0, 1000, (B, L)).astype(np.int32)
    ci = rng.integers(0, 1000, (B, K)).astype(np.int32)
    args = [jnp.asarray(a) for a in (bd, bi, cd, ci)]
    xd, xi = ops.topk_merge(*args, impl="xla")
    rd, ri = ops.topk_merge(*args, impl="ref")
    np.testing.assert_array_equal(np.asarray(xd), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(xi), np.asarray(ri))


@settings(deadline=None, max_examples=20)
@given(st.integers(1, 12), st.integers(1, 64), st.integers(2, 48),
       st.integers(0, 2**31 - 1))
def test_pairwise_ref_is_true_distance(B, N, d, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(B, d)).astype(np.float32)
    y = rng.normal(size=(N, d)).astype(np.float32)
    got = np.asarray(ref.pairwise_sq_dists(jnp.asarray(x), jnp.asarray(y)))
    want = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(2, 64), st.integers(1, 96),
       st.sampled_from([8, 16, 64, 128]), st.integers(0, 2**31 - 1))
def test_int8_distance_within_analytic_bound(B, N, d, gs, seed):
    """Property sweep for the sq8 kernels: on arbitrary shapes and scale
    grids, (1) the Pallas int8 kernel matches the dequantize oracle, and
    (2) the certified bounds computed from the exact per-vector errors
    bracket the true f32 distance — the analytic error bound the
    filter-then-rerank pipeline relies on."""
    from repro.quant import build_store, quantize_queries

    rng = np.random.default_rng(seed)
    scale = float(rng.uniform(0.1, 10.0))          # exercise the scale grid
    Y = (rng.normal(size=(N, d)) * scale).astype(np.float32)
    X = (rng.normal(size=(B, d)) * scale).astype(np.float32)
    st_ = build_store(Y, group_size=gs)
    qx, xn, xe = quantize_queries(X, st_)
    got = np.asarray(ops.pairwise_sq_dists_int8(
        qx, st_.q, st_.scales, group_size=gs, xn=xn, yn=st_.norms,
        impl="pallas_interpret"))
    want = np.asarray(ops.pairwise_sq_dists_int8(
        qx, st_.q, st_.scales, group_size=gs, impl="ref"))
    assert_allclose(got, want, rtol=1e-4, atol=1e-3 * scale ** 2)

    true = np.asarray(ref.pairwise_sq_dists(jnp.asarray(X), jnp.asarray(Y)))
    slack = jnp.asarray(np.asarray(xe)[:, None]
                        + np.asarray(st_.err)[None, :])
    lb = np.asarray(ops.quant_lower_bound(jnp.asarray(got), slack))
    ub = np.asarray(ops.quant_upper_bound(jnp.asarray(got), slack))
    tol = 1e-4 * max(d, 1) * scale ** 2
    assert (lb <= true + tol).all()
    assert (ub >= true - tol).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 6), st.integers(1, 48), st.integers(1, 80),
       st.sampled_from([8, 32, 128]), st.integers(0, 2**31 - 1))
def test_int8_rowwise_matches_pairwise_gather(B, K, d, gs, seed):
    """Rowwise (difference-form) and pairwise (dot-form) int8 kernels
    agree on gathered candidates — the two quantized-domain formulations
    compute the same d̂."""
    from repro.quant import build_store, quantize_queries

    rng = np.random.default_rng(seed)
    N = int(rng.integers(K + 1, K + 128))
    Y = rng.normal(size=(N, d)).astype(np.float32)
    st_ = build_store(Y, group_size=gs)
    qx, _, _ = quantize_queries(rng.normal(size=(B, d)).astype(np.float32),
                                st_)
    idx = rng.integers(0, N, (B, K))
    qc = jnp.asarray(np.asarray(st_.q)[idx])
    row = np.asarray(ops.rowwise_sq_dists_int8(
        qx, qc, st_.scales, group_size=gs, impl="pallas_interpret"))
    pw = np.asarray(ops.pairwise_sq_dists_int8(
        qx, st_.q, st_.scales, group_size=gs, impl="pallas_interpret"))
    assert_allclose(row, pw[np.arange(B)[:, None], idx], rtol=1e-4,
                    atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 5), st.integers(1, 64), st.integers(16, 96),
       st.integers(0, 2**31 - 1))
def test_gather_distance_property(B, K, d, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(K + 1, 300))
    vecs = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (B, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(-1, n, (B, K)).astype(np.int32))
    a = np.asarray(ops.gather_sq_dists(vecs, x, idx, impl="ref"))
    b = np.asarray(ops.gather_sq_dists(vecs, x, idx,
                                       impl="pallas_interpret"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert (np.isinf(a) == (np.asarray(idx) < 0)).all()
