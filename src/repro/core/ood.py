"""Out-of-distribution query prediction (paper §4.5, Fig. 7).

A query is predicted OOD when the mean distance d1 from the query to its
neighboring *data* points (its neighbor row in the merged index) exceeds
``factor``× the mean distance d2 from those neighbors to *their* neighbors
(2-hop from the query). d2 is read from the per-node ``mean_nbr_dist`` side
table stored at index-construction time (paper: <1% size/time overhead).

All distances here are plain L2 (the paper's thresholds are L2), hence the
sqrt on the squared-distance kernel output — under the ``ip`` metric too
(``GraphIndex.metric``), where only the merged index's edges come from the
inner product and ``mean_nbr_dist`` stays an L2 table. The test is a
ratio of distances, and −⟨x, y⟩ can be negative, so a ratio of it means
nothing. The test is a routing heuristic, not the join's semantics: the
hybrid path is sound for every query, so a wrong flag costs speed, never
pairs.

Under ``ip`` a query is also OOD when its merged-index row holds query
nodes beyond their share of the index (``n_nodes − n_data`` of
``n_nodes``): the row of an in-distribution query holds data nodes in
proportion or more. The inner-product prune keeps few edges per node
(rows of large norm dominate every candidate list), and a query from
another modality keeps mostly other queries: its data neighbours are then
one or two rows, whose L2 distances to it read like their own neighbours'
(d1/d2 ≈ 1), so the ratio test alone flags almost none, while the BFS over
data nodes alone cannot reach its results (``PERF.md``). The L2 rule is
the paper's, unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.types import NO_NODE, GraphIndex
from repro.kernels import ops

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("factor",))
def predict_ood(merged: GraphIndex, x: Array, qids: Array, *,
                factor: float = 1.5) -> Array:
    """OOD flags for queries.

    Args:
      merged: merged index G_{X∪Y} (query node ids ≥ n_data).
      x: (B, d) query vectors; qids: (B,) their node ids in the merged index.
    Returns:
      (B,) bool — True ⇒ predicted OOD ⇒ use hybrid BBFS (the rule per
      metric is the module's).
    """
    rows = merged.nbrs[qids]                                # (B, R)
    is_data = (rows != NO_NODE) & (rows < merged.n_data)
    nvecs = merged.vecs[jnp.clip(rows, 0)]                  # (B, R, d)
    d1_all = jnp.sqrt(ops.rowwise_sq_dists(x, nvecs))       # (B, R) L2
    cnt = jnp.maximum(jnp.sum(is_data, axis=1), 1)
    d1 = jnp.sum(jnp.where(is_data, d1_all, 0.0), axis=1) / cnt
    d2_all = merged.mean_nbr_dist[jnp.clip(rows, 0)]        # (B, R)
    d2 = jnp.sum(jnp.where(is_data, d2_all, 0.0), axis=1) / cnt
    # queries with no data neighbors at all are OOD by definition
    n_data_nbrs = jnp.sum(is_data, axis=1)
    ood = (n_data_nbrs == 0) | (d1 > factor * d2)
    if merged.metric == "ip":
        # ... and under ip, those whose row holds data nodes below their
        # share of the index
        n_nbrs = jnp.sum(rows != NO_NODE, axis=1)
        ood = ood | (n_data_nbrs * merged.n_nodes < n_nbrs * merged.n_data)
    return ood
