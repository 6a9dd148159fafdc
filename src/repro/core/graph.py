"""Offline graph-index construction (paper §4.4, NSG [16] style) in JAX.

Pipeline (all heavy compute jitted; thin numpy orchestration for the
connectivity repair, which is offline and O(repairs)):

  1. exact kNN graph — blocked pairwise distances (kernels.ops) with a
     running top-k merge so memory stays O(block² + N·k).
  2. RNG/MRNG edge pruning — the paper's Fig. 5 rule: walking candidates in
     ascending distance from u, keep v iff no already-kept w has
     dist(w, v) < dist(u, v). (Candidates are sorted, so dist(u,w) <
     dist(u,v) holds for every kept w automatically.) This is the property
     that guarantees each node's top-1 NN stays in its neighborhood — the
     merged index's O(1)-seed offloading rests on it.
  3. medoid navigating node.
  4. connectivity repair — NSG's tree-span: nodes unreachable from the
     medoid get attached to their nearest reachable node (extra edge slots
     are reserved for this).

The merged index G_{X∪Y} (paper §4.4) is the same construction over
concat([Y, X]) with ``n_data = |Y|``.

**Metric** (``build_index(..., metric=...)``, ``core.types.METRICS``):
the kNN sweep, the prune rule and the navigating node run on the
metric's dissimilarity — squared L2, or −⟨x, y⟩ under ``ip`` (hnswlib's
``ip`` space likewise prunes on 1 − ⟨x, y⟩). The connectivity repair and
the OOD side table stay L2 under either metric (``core/ood.py``): under
``ip`` the reachable node of largest ⟨x, y⟩ is one of a few large-norm
hubs for nearly every unreachable node, so a repair on −⟨x, y⟩ piles every
spoke onto the same full rows, each evicting the last, and never
converges; the nearest reachable node in space spreads them. Three more
steps are the ``ip`` build's own. The repair never evicts an edge
(``_repair_connectivity``). Each row's free slots end up holding the
candidates its prune dropped (``_fill_pruned``): the prune keeps a few
edges to hubs, and in-range nodes near θ are otherwise reached by almost
no edge. The build runs in a canonical row order (``build_index``), so
the index does not depend on the order of the rows. Cascade-driven builds
certify L2 bounds only, so they take ``l2``.

**Cascade-driven builds** (``build_index(..., quant="sq8")``): steps 1
and 2 are the dominant offline f32 traffic (every construction distance
streams d×4 bytes), and both are *selection* problems — top-k for the
kNN, a pairwise comparison for the prune rule — which the certified
bounds of a ``repro.quant.FilterCascade`` can resolve for all but an
ambiguous band. The kNN sweep runs on int8 codes and keeps only
candidates whose certified lower bound beats the k-th smallest certified
upper bound (a certified superset of the f32 top-k, matmul-rounding
guard included); the prune rule resolves each ``dist(w,v) < dist(u,v)``
comparison from bounds where they are decisive. Only the band is
re-computed in f32, with guards sized so the resulting neighbor lists
are identical to the plain f32 build; ``BuildStats`` reports the f32
traffic avoided (``benchmarks/bench_offline.py`` records it).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import NO_NODE, GraphIndex, check_metric
from repro.kernels import ops

Array = jax.Array
_INF = jnp.float32(jnp.inf)
# element budget of one blocked distance tile (2^28 f32 = 1 GiB)
_TILE_ELEMS = 1 << 28


@dataclasses.dataclass
class BuildStats:
    """Traffic accounting for one (cascade-driven) index build.

    Byte counts follow the repo's distance-traffic model (one candidate
    row streamed per evaluated distance): ``f32_bytes`` is what the
    cascade build actually moved through f32 distance evaluations,
    ``f32_bytes_full`` what the plain f32 build would have moved for the
    same steps, ``tier_bytes`` the compressed-tier traffic that replaced
    the difference. ``knn_pairs``/``knn_exact`` and ``prune_pairs``/
    ``prune_exact`` are the per-stage survivor counts (pairs bounded vs
    pairs needing exact f32)."""
    knn_pairs: int = 0
    knn_exact: int = 0
    prune_pairs: int = 0
    prune_exact: int = 0
    f32_bytes: int = 0
    f32_bytes_full: int = 0
    tier_bytes: int = 0

    @property
    def f32_saved_frac(self) -> float:
        if self.f32_bytes_full == 0:
            return 0.0
        return 1.0 - self.f32_bytes / self.f32_bytes_full

    def as_dict(self) -> dict:
        return dict(dataclasses.asdict(self),
                    f32_saved_frac=self.f32_saved_frac)


# ---------------------------------------------------------------------------
# 1. exact kNN graph (blocked)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "dblock", "impl",
                                             "metric"))
def _knn_block(qvecs: Array, vecs: Array, qoff: Array, *, k: int,
               dblock: int, impl: str | None, metric: str = "l2"
               ) -> tuple[Array, Array]:
    """kNN of a query block against all vecs (excluding self), via scan."""
    n = vecs.shape[0]
    nblocks = -(-n // dblock)
    npad = nblocks * dblock
    vpad = jnp.pad(vecs, ((0, npad - n), (0, 0)))
    bq = qvecs.shape[0]

    def body(carry, j):
        bd, bi = carry
        yblk = jax.lax.dynamic_slice_in_dim(vpad, j * dblock, dblock)
        d = ops.pairwise_dists(qvecs, yblk, metric=metric,
                               impl=impl)                      # (bq, dblock)
        ids = j * dblock + jnp.arange(dblock, dtype=jnp.int32)[None, :]
        ids = jnp.broadcast_to(ids, d.shape)
        valid = ids < n
        # self-exclusion: query block rows are vecs[qoff + i]
        self_ids = qoff + jnp.arange(bq, dtype=jnp.int32)
        is_self = ids == self_ids[:, None]
        d = jnp.where(valid & ~is_self, d, _INF)
        bd, bi = ops.topk_merge(bd, bi, d, ids)
        return (bd, bi), None

    bd0 = jnp.full((bq, k), _INF)
    bi0 = jnp.full((bq, k), NO_NODE, jnp.int32)
    (bd, bi), _ = jax.lax.scan(body, (bd0, bi0), jnp.arange(nblocks))
    return bd, bi


def exact_knn(vecs: Array, k: int, *, qblock: int = 512, dblock: int = 8192,
              impl: str | None = None, cascade=None,
              stats: BuildStats | None = None, metric: str = "l2"
              ) -> tuple[np.ndarray, np.ndarray]:
    """Exact kNN graph: returns (dists (N,k) f32, ids (N,k) i32), ascending
    in the metric's dissimilarity.

    With a ``cascade`` (whose confirming tier must provide upper bounds,
    i.e. carry an int8 tier) the sweep runs filter-then-rerank: certified
    bounds from the tier's codes select a superset of the f32 top-k and
    only those survivors get exact f32 distances — same neighbor lists,
    f32 traffic proportional to the survivor band (``stats``)."""
    n = vecs.shape[0]
    confirm = cascade.tier("int8") if cascade is not None else None
    if confirm is not None:
        if metric != "l2":
            raise ValueError(f"a cascade-driven build certifies L2 "
                             f"bounds; metric {metric!r} builds with quant "
                             f"'off'")
        return _cascade_knn(vecs, confirm, k, qblock=qblock, dblock=dblock,
                            impl=impl, stats=stats)
    out_d = np.empty((n, k), np.float32)
    out_i = np.empty((n, k), np.int32)
    for q0 in range(0, n, qblock):
        q1 = min(q0 + qblock, n)
        qv = vecs[q0:q1]
        bd, bi = _knn_block(qv, vecs, jnp.int32(q0), k=k, dblock=dblock,
                            impl=impl, metric=metric)
        out_d[q0:q1] = np.asarray(bd)
        out_i[q0:q1] = np.asarray(bi)
    return out_d, out_i


def _cascade_knn(vecs: Array, tier, k: int, *, qblock: int, dblock: int,
                 impl: str | None, stats: BuildStats | None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """kNN through the cascade's int8 tier: certified filter, exact
    re-rank of the survivor band.

    Soundness of the survivor set: let ``τ`` be the k-th smallest
    certified *upper* bound of a row — at least k candidates have true
    distance ≤ τ. The f32 build selects by the matmul-form kernel value
    ``d32``, which differs from the true distance by at most the
    matmul-rounding guard ``g``; every member of (and tie at the
    boundary of) the f32 top-k therefore has certified lower bound
    ≤ τ + 2g, so filtering on ``lb ≤ τ + margin`` with ``margin ≥ 2g``
    keeps a superset of the f32 selection.

    Bit-identity of the selection: survivors are re-ranked with the
    *same* matmul-form composition the f32 sweep uses (row norms + a
    gathered-column GEMM — XLA's per-entry dot is bitwise stable under
    row/column subsetting, so each survivor pair reproduces the f32
    sweep's value exactly), and the k smallest per row — ties broken by
    ascending id, matching the f32 path's stable block-scan merge — are
    the identical neighbor lists, distances included.
    """
    from repro.quant.cascade import MATMUL_GUARD

    st = tier.store
    n, d = vecs.shape
    vj = jnp.asarray(vecs, jnp.float32)
    # true-f32 row norms, computed once the same way the f32 sweep's
    # epilogue computes them (per-row minor-axis reduce)
    vn = jnp.sum(vj * vj, axis=-1)
    yn = st.norms
    max_yn = float(jnp.max(yn)) if n else 0.0
    out_d = np.full((n, k), np.inf, np.float32)
    out_i = np.full((n, k), NO_NODE, np.int32)
    n_pairs = n_exact = 0
    for q0 in range(0, n, qblock):
        q1 = min(q0 + qblock, n)
        bq = q1 - q0
        qc = tier.rows_as_queries(q0, q1)
        # generous headroom over the 2·g bound (g uses dequantized norms,
        # which track true norms only up to the quantization error)
        margin = np.asarray(4 * MATMUL_GUARD * (qc.norms + max_yn))
        # pass over data blocks: running top-k of certified upper bounds
        # (⇒ τ) while collecting lower-bound survivors vs the running τ
        # (a superset of the survivors vs the final τ — filtered below)
        bd = jnp.full((bq, k), _INF)
        bi = jnp.full((bq, k), NO_NODE, jnp.int32)
        parts: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        for j0 in range(0, n, dblock):
            j1 = min(j0 + dblock, n)
            dhat = ops.pairwise_sq_dists_int8(
                qc.q, st.q[j0:j1], st.scales, group_size=st.group_size,
                xn=qc.norms, yn=yn[j0:j1], impl=impl)
            slack = qc.err[:, None] + st.err[j0:j1][None, :]
            guard = jnp.float32(MATMUL_GUARD) * (qc.norms[:, None]
                                                 + yn[j0:j1][None, :])
            lb = ops.quant_lower_bound(jnp.maximum(dhat - guard, 0.0),
                                       slack)
            ub = ops.quant_upper_bound(dhat + guard, slack)
            ids = j0 + jnp.arange(j1 - j0, dtype=jnp.int32)[None, :]
            is_self = ids == (q0 + jnp.arange(bq, dtype=jnp.int32))[:, None]
            lb = jnp.where(is_self, _INF, lb)
            ub = jnp.where(is_self, _INF, ub)
            bd, bi = ops.topk_merge(bd, bi, ub,
                                    jnp.broadcast_to(ids, ub.shape))
            tau_run = np.asarray(bd[:, k - 1])
            keep = np.asarray(lb) <= tau_run[:, None] + margin[:, None]
            qi, yi = np.nonzero(keep)
            parts.append((qi.astype(np.int32), (yi + j0).astype(np.int32),
                          np.asarray(lb)[qi, yi]))
            n_pairs += bq * (j1 - j0)
        tau = np.asarray(bd[:, k - 1])
        qi = np.concatenate([p[0] for p in parts])
        yi = np.concatenate([p[1] for p in parts])
        plb = np.concatenate([p[2] for p in parts])
        sel = plb <= tau[qi] + margin[qi]
        qi, yi = qi[sel], yi[sel]
        n_exact += int(qi.size)
        # exact f32 re-rank of the survivor band with the f32 sweep's own
        # matmul-form arithmetic: per-row survivor lists padded to the
        # block max, gathered-column GEMM per row (bitwise equal to the
        # full sweep's entries), then per-row stable top-k by (d, id)
        order = np.lexsort((yi, qi))
        qi, yi = qi[order], yi[order]
        counts = np.bincount(qi, minlength=bq)
        S = max(int(counts.max()) if counts.size else 0, 1)
        starts = np.searchsorted(qi, np.arange(bq))
        slot = np.arange(qi.size) - starts[qi]
        colmat = np.zeros((bq, S), np.int32)
        valid = np.zeros((bq, S), bool)
        colmat[qi, slot] = yi
        valid[qi, slot] = True
        ysub = vj[jnp.asarray(colmat)]                       # (bq, S, d)
        xy = jnp.matmul(vj[q0:q1][:, None, :],
                        jnp.transpose(ysub, (0, 2, 1)),
                        precision=jax.lax.Precision.HIGHEST)[:, 0, :]
        dmat = jnp.maximum(vn[q0:q1][:, None] + vn[jnp.asarray(colmat)]
                           - 2.0 * xy, 0.0)
        dsur = np.asarray(dmat)[qi, slot]
        order = np.lexsort((yi, dsur, qi))
        qi, yi, dsur = qi[order], yi[order], dsur[order]
        rank = np.arange(qi.size) - starts[qi]
        m = rank < k
        out_d[q0 + qi[m], rank[m]] = dsur[m]
        out_i[q0 + qi[m], rank[m]] = yi[m]
    if stats is not None:
        stats.knn_pairs += n_pairs
        stats.knn_exact += n_exact
        stats.tier_bytes += n_pairs * d
        stats.f32_bytes += n_exact * d * 4
        stats.f32_bytes_full += n_pairs * d * 4
    return out_d, out_i


# ---------------------------------------------------------------------------
# 2. RNG / MRNG pruning (paper Fig. 5)
# ---------------------------------------------------------------------------

def _prune_from_lt(lt: Array, valid: Array, cand_ids: Array, R: int
                   ) -> Array:
    """The Fig. 5 keep loop, given the resolved comparison matrix
    ``lt[b, w, v] = dist(w, v) < dist(u, v)`` (shared by the f32 and
    cascade prune paths — the rule itself has one implementation)."""
    b, k = cand_ids.shape

    def body(i, keep):
        # v = candidate i; conflict if any kept w (w earlier => closer to u)
        # with dist(w, v) < dist(u, v)
        conflict = jnp.any(keep & lt[:, :, i], axis=1)
        kept_so_far = jnp.sum(keep, axis=1)
        ok = valid[:, i] & ~conflict & (kept_so_far < R)
        return keep.at[:, i].set(ok)

    keep = jax.lax.fori_loop(0, k, body, jnp.zeros((b, k), bool))
    # compact kept ids to the left, preserving ascending order
    pos = jnp.cumsum(keep, axis=1) - 1                        # target slot
    pos = jnp.where(keep, pos, R)                             # dump to R
    out = jnp.full((b, R + 1), NO_NODE, jnp.int32)
    out = out.at[jnp.arange(b)[:, None], pos].set(
        jnp.where(keep, cand_ids, NO_NODE))
    return out[:, :R]


def _pair_sq_dists(cvecs: Array, metric: str = "l2") -> Array:
    """(b, k, d) gathered candidate rows → (b, k, k) matmul-form pairwise
    squared distances (the prune rule's comparison values); under ``ip``,
    −⟨w, v⟩."""
    if metric == "ip":
        return -jnp.einsum("bkd,bjd->bkj", cvecs.astype(jnp.float32),
                           cvecs.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)
    cn = jnp.sum(cvecs.astype(jnp.float32) ** 2, axis=-1)    # (b, k)
    cc = jnp.einsum("bkd,bjd->bkj", cvecs.astype(jnp.float32),
                    cvecs.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(cn[:, :, None] + cn[:, None, :] - 2.0 * cc, 0.0)


@functools.partial(jax.jit, static_argnames=("R", "metric"))
def _rng_prune_block(vecs: Array, cand_ids: Array, cand_d: Array, *, R: int,
                     metric: str = "l2") -> Array:
    """Prune candidate lists (ascending by distance) to RNG edges, max R.

    Args:
      vecs: (N, d) all vectors.
      cand_ids: (b, k) candidate ids per node (NO_NODE padded, ascending d).
      cand_d: (b, k) dissimilarities node→candidate (squared L2, or
        −⟨u, v⟩ under ``ip``).
    Returns:
      (b, R) pruned neighbor ids (NO_NODE padded, ascending by distance).
    """
    pair = _pair_sq_dists(vecs[jnp.clip(cand_ids, 0)], metric)
    valid = cand_ids != NO_NODE
    return _prune_from_lt(pair < cand_d[:, None, :], valid, cand_ids, R)


@functools.partial(jax.jit, static_argnames=("R",))
def _rng_prune_block_cascade(vecs: Array, q: Array, norms: Array,
                             err: Array, sd: Array, cand_ids: Array,
                             cand_d: Array, *, R: int
                             ) -> tuple[Array, Array, Array]:
    """Cascade-driven RNG pruning: resolve each ``dist(w,v) < dist(u,v)``
    comparison from certified int8 bounds where they are decisive, and
    gather f32 rows only for candidates touching an ambiguous pair.

    The bounds bracket the *true* pair distance; the f32 path compares
    the matmul-form kernel value, which sits within the matmul-rounding
    guard of the truth — so a comparison is only certain when the bound
    clears ``cand_d`` by that guard on the right side. Ambiguous pairs
    are recomputed with the *same* matmul-form arithmetic as the f32
    path, over a gathered tensor whose non-participating rows collapse
    to row 0 (fixed shape; HBM traffic proportional to the band).

    Returns ``(pruned (b, R), n_f32_rows (), n_amb_pairs ())``.
    """
    from repro.quant.cascade import MATMUL_GUARD

    b, k = cand_ids.shape
    safe = jnp.clip(cand_ids, 0)
    codes = q[safe]                                          # (b, k, d) i8
    deq = codes.astype(jnp.float32) * sd                     # dequantized
    pair_hat = _pair_sq_dists(deq)
    nh = norms[safe]                                         # (b, k)
    eh = err[safe]
    nsum = nh[:, :, None] + nh[:, None, :]
    guard_hat = jnp.float32(MATMUL_GUARD) * nsum
    slack = eh[:, :, None] + eh[:, None, :]
    lb = ops.quant_lower_bound(jnp.maximum(pair_hat - guard_hat, 0.0),
                               slack)
    ub = ops.quant_upper_bound(pair_hat + guard_hat, slack)
    # f32-kernel rounding margin (2× headroom: nh are dequantized norms)
    g32 = jnp.float32(2 * MATMUL_GUARD) * nsum
    cd = cand_d[:, None, :]
    sure_lt = ub + g32 < cd
    sure_ge = lb - g32 >= cd
    valid = cand_ids != NO_NODE
    vpair = valid[:, :, None] & valid[:, None, :]
    amb = vpair & ~(sure_lt | sure_ge)
    # f32 rows only for candidates participating in an ambiguous pair
    needed = jnp.any(amb, axis=2) | jnp.any(amb, axis=1)
    cvecs = vecs[jnp.where(needed, safe, 0)]
    pair32 = _pair_sq_dists(cvecs)
    lt = jnp.where(amb, pair32 < cd, sure_lt)
    out = _prune_from_lt(lt, valid, cand_ids, R)
    return (out, jnp.sum(needed).astype(jnp.int32),
            jnp.sum(amb).astype(jnp.int32))


# ---------------------------------------------------------------------------
# 3.+4. medoid & connectivity repair
# ---------------------------------------------------------------------------

def _medoid(vecs: Array, sample: int = 4096, seed: int = 0,
            metric: str = "l2") -> int:
    n = vecs.shape[0]
    rng = np.random.default_rng(seed)
    idx = rng.choice(n, size=min(sample, n), replace=False)
    sub = vecs[jnp.asarray(idx)]
    d = ops.pairwise_dists(sub, sub, metric=metric)
    return int(idx[int(np.argmin(np.asarray(jnp.sum(d, axis=1))))])


def _reachable(nbrs: np.ndarray, start: int) -> np.ndarray:
    """BFS reachability over the dense neighbor table (offline, numpy)."""
    n = nbrs.shape[0]
    seen = np.zeros(n, bool)
    seen[start] = True
    frontier = np.array([start])
    while frontier.size:
        nxt = nbrs[frontier].reshape(-1)
        nxt = nxt[nxt >= 0]
        nxt = nxt[~seen[nxt]]
        if nxt.size == 0:
            break
        nxt = np.unique(nxt)
        seen[nxt] = True
        frontier = nxt
    return seen


def _add_reverse_edges(nbrs: np.ndarray) -> np.ndarray:
    """Insert backward edges into free slots (NSG post-pruning step).

    RNG pruning yields directed edges; without back-edges a search seeded
    inside a tight cluster cannot climb back out toward other regions
    (DESIGN §2 — this is what makes work-sharing seeds navigable). For each
    edge u→v we add v→u when v has room and the edge is absent.
    """
    n, R = nbrs.shape
    u = np.repeat(np.arange(n, dtype=np.int64), R)
    v = nbrs.reshape(-1).astype(np.int64)
    ok = v >= 0
    u, v = u[ok], v[ok]
    order = np.argsort(v, kind="stable")
    u, v = u[order], v[order]
    starts = np.searchsorted(v, np.arange(n))
    ends = np.searchsorted(v, np.arange(n) + 1)
    for node in range(n):
        s, e = starts[node], ends[node]
        if s == e:
            continue
        row = nbrs[node]
        free = np.flatnonzero(row == NO_NODE)
        if free.size == 0:
            continue
        have = set(row[row >= 0].tolist())
        j = 0
        for cand in u[s:e]:
            if j >= free.size:
                break
            if cand not in have:
                nbrs[node, free[j]] = cand
                have.add(int(cand))
                j += 1
    return nbrs


def _fill_pruned(nbrs: np.ndarray, cand_i: np.ndarray,
                 block: int = 16384) -> np.ndarray:
    """Fill each row's free slots with the candidates its prune dropped,
    nearest first (hnswlib's ``keepPrunedConnections``). Adding edges only
    adds paths: what the repair reached stays reachable."""
    n, R = nbrs.shape
    for b0 in range(0, n, block):
        nb, ci = nbrs[b0:b0 + block], cand_i[b0:b0 + block]
        new = (ci >= 0) & ~np.any(ci[:, :, None] == nb[:, None, :], axis=2)
        free = nb == NO_NODE
        # the j-th new candidate of a row goes to its j-th free slot
        rank_new = np.cumsum(new, axis=1) - 1
        n_take = np.minimum(new.sum(1), free.sum(1))
        take = new & (rank_new < n_take[:, None])
        slot_of = np.argsort(~free, axis=1, kind="stable")  # free slots first
        rows, cols = np.nonzero(take)
        nb[rows, slot_of[rows, rank_new[rows, cols]]] = ci[rows, cols]
    return nbrs


def _repair_connectivity(vecs_np: np.ndarray, nbrs: np.ndarray, start: int,
                         impl: str | None, *, spare_full: bool = False
                         ) -> np.ndarray:
    """Attach unreachable nodes to their nearest reachable node in L2
    (NSG §tree), under either metric (module header).

    A node whose host row is full evicts the host's last edge. With
    ``spare_full`` (the ``ip`` build) only rows with a free slot host a
    node and no edge is evicted, so reachability only grows: the ``ip``
    prune leaves hubs of full rows that most unreachable nodes lie
    nearest to, and evicting there cuts off what the last round attached.
    """
    n, R = nbrs.shape
    for _ in range(64):  # bounded repair rounds
        seen = _reachable(nbrs, start)
        missing = np.flatnonzero(~seen)
        if missing.size == 0:
            break
        reach_ids = np.flatnonzero(seen)
        if spare_full:
            reach_ids = reach_ids[np.any(nbrs[reach_ids] == NO_NODE, axis=1)]
            if reach_ids.size == 0:
                break
        # nearest reachable node for each missing node (exact, blocked
        # over the missing side so the distance tile stays ~1 GB; the
        # argmin runs on the device, so only ids come back)
        rv = jnp.asarray(vecs_np[reach_ids])
        step = max(1, _TILE_ELEMS // reach_ids.size)
        host = np.concatenate([
            reach_ids[np.asarray(jnp.argmin(ops.pairwise_sq_dists(
                jnp.asarray(vecs_np[missing[m0:m0 + step]]), rv,
                impl=impl), axis=1))]
            for m0 in range(0, missing.size, step)])
        for m, h in zip(missing, host):
            row = nbrs[h]
            free = np.flatnonzero(row == NO_NODE)
            if free.size:
                nbrs[h, free[0]] = m
            elif not spare_full:
                nbrs[h, R - 1] = m  # evict farthest edge (last slot)
    return nbrs


# ---------------------------------------------------------------------------
# public builders
# ---------------------------------------------------------------------------

def _mean_nbr_dist(rows: Array, vecs: Array, nbrs: Array, *,
                   impl: str | None) -> Array:
    """Mean L2 distance from each row to its valid neighbors."""
    nd = jnp.sqrt(ops.rowwise_sq_dists(rows, vecs[jnp.clip(nbrs, 0)],
                                       impl=impl))
    mask = nbrs != NO_NODE
    return jnp.sum(jnp.where(mask, nd, 0.0), axis=1) / jnp.maximum(
        jnp.sum(mask, axis=1), 1)


def build_index(vecs, *, k: int = 48, degree: int = 32, n_data: int | None = None,
                prune_block: int = 1024, seed: int = 0,
                impl: str | None = None, style: str = "nsg",
                quant: str | None = None,
                build_stats: BuildStats | None = None,
                metric: str = "l2") -> GraphIndex:
    """Build a graph index over ``vecs``.

    Args:
      vecs: (N, d) float array (numpy or jax).
      k: candidate-list size for pruning (kNN width).
      degree: max out-degree R after pruning; one slot is reserved headroom
        for connectivity-repair edges.
      n_data: number of *data* nodes (ids [0, n_data)); defaults to N
        (plain data index). For a merged index pass |Y| with vecs =
        concat([Y, X]).
      style: "nsg" (RNG/MRNG pruning — the paper's default [16]) or "nsw"
        (no diversity pruning: top-R kNN edges — the flat navigable-small-
        world graph, our TPU-shape stand-in for HNSW in the paper's Fig. 15
        index-type ablation; true HNSW hierarchy does not map to the dense
        neighbor-table traversal, see DESIGN §2).
      quant: a quant mode name (``core.types.QUANT_MODES``) or a prebuilt
        ``FilterCascade`` over ``vecs`` — drives the kNN sweep and the
        RNG prune through certified bounds (identical edges, f32 traffic
        cut to the ambiguous band; see the module header). ``build_stats``
        collects the traffic accounting.
      metric: the space of the index (``core.types.METRICS``); see the
        module header.
    """
    check_metric(metric)
    kw = dict(k=k, degree=degree, prune_block=prune_block, seed=seed,
              impl=impl, style=style, quant=quant, build_stats=build_stats,
              metric=metric)
    if metric != "ip":
        return _build_index(vecs, n_data=n_data, **kw)
    # Under ip, which edges survive at the full rows of the hubs (reverse
    # edges, repair spokes) depends on node ids. The build runs in a
    # canonical order of the rows, data and queries each sorted by their
    # values, so the index depends on the vectors and not on their order.
    v = np.asarray(vecs)
    nd = v.shape[0] if n_data is None else int(n_data)
    perm = np.concatenate([
        b0 + np.lexsort(v[b0:b1, :2].T[::-1])
        for b0, b1 in ((0, nd), (nd, v.shape[0])) if b1 > b0])
    inv = np.argsort(perm)
    g = _build_index(jnp.asarray(v[perm]), n_data=n_data, **kw)
    nb = np.asarray(g.nbrs)[inv]
    nb = np.where(nb >= 0, perm[np.clip(nb, 0, None)], NO_NODE)
    return dataclasses.replace(
        g, vecs=jnp.asarray(vecs), nbrs=jnp.asarray(nb, jnp.int32),
        start=jnp.int32(perm[int(g.start)]),
        mean_nbr_dist=g.mean_nbr_dist[jnp.asarray(inv)])


def _build_index(vecs, *, k: int, degree: int, n_data: int | None,
                 prune_block: int, seed: int, impl: str | None, style: str,
                 quant, build_stats: BuildStats | None,
                 metric: str) -> GraphIndex:
    """``build_index`` in the rows' own order."""
    vecs = jnp.asarray(vecs)
    n = vecs.shape[0]
    d = int(vecs.shape[1])
    k = min(k, n - 1)
    cascade = None
    if quant is not None and quant != "off":
        if isinstance(quant, str):
            from repro.quant.cascade import TIERS_BY_MODE, build_cascade
            # the build consults only the confirming int8 tier (pairwise
            # sweeps gain nothing from a 1-bit pre-pass whose bounds the
            # int8 matmul recomputes anyway) — skip building tiers the
            # mode stacks above it
            mode = "sq8" if "int8" in TIERS_BY_MODE[quant] else quant
            cascade = build_cascade(vecs, mode)
        else:
            cascade = quant
    cand_d, cand_i = exact_knn(vecs, k, impl=impl, cascade=cascade,
                               stats=build_stats, metric=metric)
    nbrs = np.empty((n, degree), np.int32)
    cand_d_j = jnp.asarray(cand_d)
    cand_i_j = jnp.asarray(cand_i)
    int8_tier = cascade.tier("int8") if cascade is not None else None
    if style == "nsw":
        half = max(degree // 2, 1)   # leave slots for reverse edges
        top = np.asarray(cand_i_j[:, :half], np.int32)
        nbrs[:, :half] = top
        nbrs[:, half:] = NO_NODE
    elif int8_tier is not None:
        from repro.quant.store import dim_scales
        st = int8_tier.store
        sd = dim_scales(st.scales, d, st.group_size)
        n_rows = n_amb = 0
        for b0 in range(0, n, prune_block):
            b1 = min(b0 + prune_block, n)
            out, rows, amb = _rng_prune_block_cascade(
                vecs, st.q, st.norms, st.err, sd, cand_i_j[b0:b1],
                cand_d_j[b0:b1], R=degree)
            nbrs[b0:b1] = np.asarray(out)
            n_rows += int(rows)
            n_amb += int(amb)
        if build_stats is not None:
            n_cand = int((cand_i >= 0).sum())
            build_stats.prune_pairs += n_cand * k
            build_stats.prune_exact += n_amb
            build_stats.tier_bytes += n_cand * d
            build_stats.f32_bytes += n_rows * d * 4
            build_stats.f32_bytes_full += n_cand * d * 4
    else:
        for b0 in range(0, n, prune_block):
            b1 = min(b0 + prune_block, n)
            nbrs[b0:b1] = np.asarray(_rng_prune_block(
                vecs, cand_i_j[b0:b1], cand_d_j[b0:b1], R=degree,
                metric=metric))
    start = _medoid(vecs, seed=seed, metric=metric)
    vecs_np = np.asarray(vecs)
    nbrs = _add_reverse_edges(nbrs)
    nbrs = _repair_connectivity(vecs_np, nbrs, start, impl,
                                spare_full=metric == "ip")
    nbrs = _add_reverse_edges(nbrs)  # make repair spokes two-way as well
    if metric == "ip":
        nbrs = _fill_pruned(nbrs, cand_i)
    # OOD side table (paper §4.5): mean L2 (not squared) neighbor distance,
    # in row blocks — the gathered (rows, R, d) neighbor tensor of a whole
    # million-row table would not fit in device memory (the rowwise
    # kernel pads R to a 128-lane tile).
    nbrs_j = jnp.asarray(nbrs)
    rows = max(1, _TILE_ELEMS // (max(degree, 128) * max(d, 1)))
    mnd = jnp.concatenate([
        _mean_nbr_dist(vecs[b0:b0 + rows], vecs, nbrs_j[b0:b0 + rows],
                       impl=impl)
        for b0 in range(0, n, rows)])
    return GraphIndex(vecs=vecs, nbrs=nbrs_j, start=jnp.int32(start),
                      mean_nbr_dist=mnd,
                      n_data=int(n if n_data is None else n_data),
                      metric=metric)


def build_merged_index(Y, X, **kw) -> GraphIndex:
    """Merged index G_{X∪Y} (paper §4.4): data ids [0,|Y|), query ids after."""
    Y = jnp.asarray(Y)
    X = jnp.asarray(X)
    return build_index(jnp.concatenate([Y, X], axis=0), n_data=Y.shape[0], **kw)
