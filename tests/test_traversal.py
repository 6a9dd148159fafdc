"""The traversal's in-batch dedup and beam merges against numpy references.

``_probe`` keeps, in each row, the first slot (in original order) of every
valid id that is not ``NO_NODE``, not already in the lane's visited bitmap
and, with ``traverse_nondata`` off, a data row; the bitmap gains exactly
the kept ids. ``_beam_merge`` and ``_hybrid_merge`` keep the ``L`` first
entries of numpy's stable argsort of their key.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import traversal
from repro.core.types import NO_NODE

N_DATA, N_NODES, DIM = 200, 256, 8


def _probe_rows(rng, B, K):
    """Candidate rows that exercise every rule of the dedup, then random
    rows with many repeats and a random mask."""
    cand = rng.integers(0, min(K, N_NODES), (B, K)).astype(np.int32)
    valid = rng.random((B, K)) < 0.8
    valid[0] = False                                  # all slots masked
    cand[1] = 17                                      # one id everywhere
    valid[1] = True
    cand[2] = NO_NODE                                 # no node at all
    valid[2] = True
    cand[3] = rng.integers(N_DATA, N_NODES, K)        # non-data ids ...
    cand[3, ::2] = rng.integers(0, N_DATA, (K + 1) // 2)  # ... among data
    valid[3] = True
    cand[4, ::3] = NO_NODE                            # NO_NODE among repeats
    visited = np.zeros((B, traversal.bitmap_words(N_NODES)), np.uint32)
    for b, c in zip(*np.nonzero(rng.random((B, N_NODES)) < 0.1)):
        visited[b, c >> 5] |= np.uint32(1 << (c & 31))
    return cand, valid, visited


def _probe_reference(cand, valid, visited, traverse_nondata):
    keep = np.zeros_like(valid)
    vis = visited.copy()
    for b in range(cand.shape[0]):
        seen = set()
        for k, c in enumerate(cand[b]):
            if (not valid[b, k] or c == NO_NODE
                    or (not traverse_nondata and c >= N_DATA)
                    or visited[b, c >> 5] >> (c & 31) & 1 or c in seen):
                continue
            seen.add(c)
            keep[b, k] = True
            vis[b, c >> 5] |= np.uint32(1 << (c & 31))
    return keep, keep.sum(axis=1), vis


@pytest.mark.parametrize("traverse_nondata", [False, True])
@pytest.mark.parametrize("K", [16, 32, 128, 160])
def test_probe_dedup_keeps_first_occurrence(K, traverse_nondata):
    rng = np.random.default_rng(K)
    B = 12
    vecs = rng.standard_normal((N_NODES, DIM)).astype(np.float32)
    x = rng.standard_normal((B, DIM)).astype(np.float32)
    cand, valid, visited = _probe_rows(rng, B, K)

    dist, ub, v, vis, n_new, n_esc = traversal._probe(
        *map(jnp.asarray, (vecs, x, cand, valid, visited)), n_data=N_DATA,
        traverse_nondata=traverse_nondata, dist_impl=None)

    keep, n_ref, vis_ref = _probe_reference(cand, valid, visited,
                                            traverse_nondata)
    np.testing.assert_array_equal(np.asarray(v), keep)
    np.testing.assert_array_equal(np.asarray(n_new), n_ref)
    np.testing.assert_array_equal(np.asarray(vis), vis_ref)
    assert keep[1].sum() == 1 and not keep[0].any() and not keep[2].any()
    assert keep[3].any()
    assert traverse_nondata or not (keep[3] & (cand[3] >= N_DATA)).any()
    want = ((x[:, None, :] - vecs[np.where(keep, cand, 0)]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(dist)[keep], want[keep],
                               rtol=1e-5, atol=1e-5)
    assert np.isinf(np.asarray(dist)[~keep]).all()
    np.testing.assert_array_equal(np.asarray(ub), np.asarray(dist))
    assert not np.asarray(n_esc).any()


def _merge_inputs(rng, B, L, Kc):
    """Beam and candidates with many ties: distances from a few values,
    +inf among them."""
    def part(n):
        d = rng.integers(0, 4, (B, n)).astype(np.float32)
        d[rng.random((B, n)) < 0.2] = np.inf
        i = rng.integers(0, 1000, (B, n)).astype(np.int32)
        e = rng.random((B, n)) < 0.5
        u = d + rng.integers(0, 3, (B, n)).astype(np.float32)
        return d, i, e, u
    return part(L), part(Kc)


@pytest.mark.parametrize("merge", ["beam", "hybrid", "hybrid_protected"])
def test_merge_keeps_stable_argsort_prefix(merge):
    rng = np.random.default_rng(len(merge))
    B, L, Kc = 8, 16, 40
    (bd, bi, be, bu), (cd, ci, ce, cu) = _merge_inputs(rng, B, L, Kc)
    alld, alli, alle, allu = (np.concatenate(p, axis=1) for p in
                              ((bd, cd), (bi, ci), (be, ce), (bu, cu)))
    key, th = alld, None
    if merge == "beam":
        got = traversal._beam_merge(bd, bi, be, cd, ci, ce)
        cols = (alld, alli, alle)
    else:
        if merge == "hybrid_protected":
            # protected entries (ub < th) tie with each other and sort
            # ahead of every unprotected one
            th = np.float32(2.5)
            key = np.where(allu < th, allu - np.float32(1e30), alld)
            assert (allu < th).sum() > B and (key < -1e29).any()
        got = traversal._hybrid_merge(bd, bi, be, bu, cd, ci, ce, cu,
                                      protect_th2=th)
        cols = (alld, alli, alle, allu)
    order = np.argsort(key, axis=1, kind="stable")[:, :L]
    assert len(got) == len(cols)
    for g, c in zip(got, cols):
        np.testing.assert_array_equal(np.asarray(g),
                                      np.take_along_axis(c, order, axis=1))
