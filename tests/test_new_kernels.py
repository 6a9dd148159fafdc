"""Interpret-mode validation of the gather-distance Pallas kernel, and the
``lax.top_k`` beam merge, against the pure-jnp oracles. Hypothesis sweeps
live in
tests/test_kernel_properties.py (they self-skip without the dev extra)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,d,B,K", [(64, 32, 4, 8), (200, 64, 8, 16),
                                     (128, 48, 3, 7)])
def test_gather_sq_dists_matches_ref(n, d, B, K):
    rng = np.random.default_rng(n + d)
    vecs = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (B, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(-1, n, (B, K)).astype(np.int32))
    a = ops.gather_sq_dists(vecs, x, idx, impl="ref")
    b = ops.gather_sq_dists(vecs, x, idx, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_topk_merge_keeps_smallest():
    bd = jnp.asarray([[0.1, 0.5, jnp.inf, jnp.inf]])
    bi = jnp.asarray([[10, 11, -1, -1]], jnp.int32)
    cd = jnp.asarray([[0.3, 0.05, 0.7]])
    ci = jnp.asarray([[20, 21, 22]], jnp.int32)
    for impl in ("ref", "xla"):
        rd, ri = ops.topk_merge(bd, bi, cd, ci, impl=impl)
        np.testing.assert_allclose(np.asarray(rd[0]),
                                   [0.05, 0.1, 0.3, 0.5], rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(ri[0]), [21, 10, 20, 11])


def test_topk_merge_rejects_unknown_impl():
    z = jnp.zeros((1, 2))
    i = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="pallas"):
        ops.topk_merge(z, i, z, i, impl="pallas")
