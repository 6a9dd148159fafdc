"""Batched TPU-native graph traversal (paper Alg. 2 & 4, adapted per DESIGN §2).

The paper's single-thread pointer-chasing loops become batched, fixed-shape
`lax.while_loop`s over a wave of B queries:

  * priority queue  → sorted beam (L entries), merged by one stable row
                      sort that carries ids, flags and bounds as operands;
  * `visited` set   → per-lane uint32 bitmap in HBM: one word lookup per
                      candidate slot, then a bit-scatter with `.at[].add`,
                      safe because candidates are deduped so every
                      (word, bit) is contributed at most once;
  * in-batch dedup  → a stable row sort of (id, slot) flags every repeat
                      after an id's first slot; a second row sort, keyed
                      on the slots, returns the flags to their own slots
                      (no per-slot gather or scatter);
  * per-node dist   → one fused rowwise-distance kernel per iteration over
                      all lanes' gathered neighbor rows (paper C4 hot spot);
  * early stopping  → per-lane plateau counters; converged lanes are masked
                      and the loop exits when all lanes converge.

Distance-computation counts (`n_dist`) replicate the paper's work metric
exactly: a distance is counted once per (query, node) — the shared-visited
invariant of Alg. 2 — enforced by the bitmap plus in-batch dedup. Two
counters measure the work the fixed shapes do besides: `n_slots`, the
candidate slots the probes evaluate (B × K per probe, masked slots
included), and `n_lane_iters`, the loop iterations in which each lane was
still active. The loop bodies' phases carry `jax.named_scope`s (`select`,
`gather`, `distance`, `visited`, `merge`) that name their device ops.

Distances are the index's metric (``GraphIndex.metric``): squared L2, or
−⟨x, y⟩ under ``ip``; thresholds enter as ``core.types.theta_key`` (θ² or
θ), and every test is ``dist < key``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import NO_NODE, GraphIndex, TraversalConfig, theta_key
from repro.kernels import ops

Array = jax.Array
_INF = jnp.float32(jnp.inf)
_SORT_PAD = jnp.int32(2**30)
# Offset that sorts beam entries protected by a certified upper bound
# ahead of every unprotected entry (distances are finite f32 ≪ 1e30).
_PROTECT_OFF = jnp.float32(1e30)


def bitmap_words(n_nodes: int) -> int:
    return -(-n_nodes // 32)


# ---------------------------------------------------------------------------
# probing: distances + visited-dedup for a (B, K) candidate id matrix
# ---------------------------------------------------------------------------

def cascade_bounds(cascade, qc, cand: Array, valid: Array, esc_th2, *,
                   dist_impl: str | None
                   ) -> tuple[Array, Array, Array]:
    """Walk gathered candidates through a ``FilterCascade``'s tier chain.

    Tier 0 bounds every candidate; each subsequent tier evaluates only the
    *escalation set* — candidates whose running certified lower bound is
    still below ``esc_th2`` (θ²). Pruned candidates' gather indices
    collapse to row 0, so each tier's HBM traffic stays proportional to
    the previous tier's survivors. Escalated candidates take the ``max``
    of lower bounds (both certified ⇒ the max is the tighter certified
    bound, and the chain lb₀ ≤ lb₁ ≤ … ≤ d stays monotone).

    Pruned candidates keep their certified floor (≥ θ², so they can never
    pool or satisfy a found-test) but are *ordered* by the pruning tier's
    navigation estimate where it provides one — the certified bound
    compresses all far candidates toward θ², which would erase the greedy
    phase's navigation gradient. Ordering may use an estimate; threshold
    tests only ever see certified bounds.

    Returns ``(dist, ub, n_esc)``: the navigation/threshold distance per
    candidate, a certified upper bound (+inf where no tier with upper
    bounds evaluated the candidate — consumed by the hybrid beam's
    eviction guard), and the per-lane count of candidates escalated into
    tier 1 (the ``n_esc8`` statistic).
    """
    B = cand.shape[0]
    lb = ub = est = None
    esc = valid
    n_esc = jnp.zeros((B,), jnp.int32)
    for i, (tier, q) in enumerate(zip(cascade.tiers, qc)):
        if i == 0:
            idx = cand
        else:
            esc = esc & (lb < esc_th2)
            if i == 1:
                n_esc = jnp.sum(esc, axis=1).astype(jnp.int32)
            idx = jnp.where(esc, cand, 0)
        tlb, tub, test = tier.gather_bounds(q, idx, impl=dist_impl)
        lb = tlb if i == 0 else jnp.where(esc, jnp.maximum(lb, tlb), lb)
        if tub is not None:
            tub = tub if i == 0 else jnp.where(esc, tub, _INF)
            ub = tub if ub is None else jnp.minimum(ub, tub)
        if test is not None and est is None:
            est = test
    dist = lb if est is None else jnp.where(esc, lb, jnp.maximum(lb, est))
    if ub is None:
        ub = jnp.full(lb.shape, _INF)
    return dist, ub, n_esc


def _probe(vecs: Array, x: Array, cand: Array, valid: Array, visited: Array,
           *, n_data: int, traverse_nondata: bool, dist_impl: str | None,
           cascade=None, qc=None, esc_th2=None, metric: str = "l2"
           ) -> tuple[Array, Array, Array, Array, Array, Array]:
    """Compute distances to candidate ids with dedup + visited masking.

    Args:
      vecs: (N, d) node vectors; x: (B, d) queries.
      cand: (B, K) candidate node ids (NO_NODE allowed); valid: (B, K).
      visited: (B, W) uint32 bitmap.
      cascade/qc/esc_th2: optional ``FilterCascade`` over ``vecs`` +
        queries encoded on its tiers' grids (``cascade.encode``) + the
        escalation threshold θ². When given, distances are *certified
        lower bounds* walked through the tier chain (``cascade_bounds``),
        so downstream `< θ²` tests accept a superset; the wave runner
        re-ranks pooled survivors exactly.
      metric: the index's metric; the exact path's distances are
        ``ops.rowwise_dists`` of it.
    Returns:
      (dist (B,K) f32 — +inf at invalid, ub (B,K) certified upper bounds
       (= dist on the exact f32 path), valid (B,K), new_visited,
       n_new (B,), n_esc (B,) — candidates escalated into tier 1).
    """
    B, K = cand.shape
    with jax.named_scope("visited"):
        valid = valid & (cand != NO_NODE)
        if not traverse_nondata:
            valid = valid & (cand < n_data)
        cand_c = jnp.where(valid, cand, 0)
        # visited test
        w = (cand_c >> 5).astype(jnp.int32)
        bit = jnp.uint32(1) << (cand_c & 31).astype(jnp.uint32)
        words = jnp.take_along_axis(visited, w, axis=1)
        valid = valid & ((words & bit) == 0)
        # in-batch dedup (two expanded nodes sharing a neighbor): a stable
        # row sort carries each slot's position with its id, so the first
        # occurrence of an id is the one kept; sorting the flags by those
        # positions (a permutation) puts each back in its own slot
        sort_key = jnp.where(valid, cand, _SORT_PAD)
        pos = jax.lax.broadcasted_iota(jnp.int32, (B, K), 1)
        sorted_ids, order = jax.lax.sort((sort_key, pos), dimension=1,
                                         is_stable=True, num_keys=1)
        dup = jnp.concatenate(
            [jnp.zeros((B, 1), bool),
             sorted_ids[:, 1:] == sorted_ids[:, :-1]],
            axis=1) & (sorted_ids != _SORT_PAD)
        _, keep = jax.lax.sort((order, ~dup), dimension=1, num_keys=1)
        valid = valid & keep
    # distances (masked)
    n_esc = jnp.zeros((B,), jnp.int32)
    if cascade is not None:
        with jax.named_scope("distance"):
            dist, ub, n_esc = cascade_bounds(cascade, qc, cand_c, valid,
                                             esc_th2, dist_impl=dist_impl)
    else:
        with jax.named_scope("gather"):
            cvec = vecs[cand_c]                             # (B, K, d)
        with jax.named_scope("distance"):
            dist = ops.rowwise_dists(x, cvec, metric=metric,
                                     impl=dist_impl)
        ub = dist
    dist = jnp.where(valid, dist, _INF)
    ub = jnp.where(valid, ub, _INF)
    with jax.named_scope("visited"):
        # mark visited: deduped ⇒ each (word,bit) contributed once ⇒
        # add == or
        add = jnp.where(valid, bit, jnp.uint32(0))
        lane = jnp.arange(B, dtype=jnp.int32)[:, None]
        visited = visited.at[lane, w].add(add)
    n_new = jnp.sum(valid, axis=1).astype(jnp.int32)
    return dist, ub, valid, visited, n_new, n_esc


def _expand(index_vecs: Array, index_nbrs: Array, x: Array, sel_ids: Array,
            sel_valid: Array, visited: Array, *, n_data: int,
            traverse_nondata: bool, dist_impl: str | None,
            cascade=None, qc=None, esc_th2=None, metric: str = "l2"):
    """Gather neighbor rows of selected nodes and probe them."""
    B, E = sel_ids.shape
    R = index_nbrs.shape[1]
    with jax.named_scope("gather"):
        rows = index_nbrs[jnp.clip(sel_ids, 0)]             # (B, E, R)
    cand = rows.reshape(B, E * R)
    valid = jnp.broadcast_to(sel_valid[:, :, None], (B, E, R)).reshape(B, E * R)
    dist, ub, valid, visited, n_new, n_esc = _probe(
        index_vecs, x, cand, valid, visited, n_data=n_data,
        traverse_nondata=traverse_nondata, dist_impl=dist_impl,
        cascade=cascade, qc=qc, esc_th2=esc_th2, metric=metric)
    return cand, dist, ub, valid, visited, n_new, n_esc


def _sorted_prefix(L: int, key: Array, *cols: Array) -> tuple[Array, ...]:
    """``(key, *cols)`` reordered by a stable ascending sort of ``key``
    along each row, first ``L`` entries; the columns ride in the sort as
    operands, so no permutation is applied afterwards."""
    out = jax.lax.sort((key, *cols), dimension=1, is_stable=True,
                       num_keys=1)
    return tuple(c[:, :L] for c in out)


def _beam_merge(bd, bi, bexp, cd, ci, cexp):
    """Merge beam with candidates, keep L smallest; carry expanded flags."""
    L = bd.shape[1]
    alld = jnp.concatenate([bd, cd], axis=1)
    alli = jnp.concatenate([bi, ci], axis=1)
    alle = jnp.concatenate([bexp, cexp], axis=1)
    return _sorted_prefix(L, alld, alli, alle)


def _hybrid_merge(bd, bi, bexp, bub, cd, ci, cexp, cub, *, protect_th2):
    """Merge the hybrid out-range beam, keeping L entries; carry certified
    upper bounds alongside.

    Eviction order is the navigation distance — except that entries whose
    certified upper bound beats ``protect_th2`` sort ahead of every
    unprotected entry (ordered among themselves by that upper bound).
    Under quantized modes navigation distances are lower bounds and
    estimates, which can compress or reorder genuinely-near candidates
    toward the back of a full beam; the guard makes eviction unable to
    drop a candidate that is *certifiably* within the protection radius —
    the per-query recall floor for OOD queries. ``protect_th2 = None``
    (exact f32 or guard disabled) reduces to a plain distance merge."""
    L = bd.shape[1]
    alld = jnp.concatenate([bd, cd], axis=1)
    alli = jnp.concatenate([bi, ci], axis=1)
    alle = jnp.concatenate([bexp, cexp], axis=1)
    allu = jnp.concatenate([bub, cub], axis=1)
    if protect_th2 is None:
        return _sorted_prefix(L, alld, alli, alle, allu)
    key = jnp.where(allu < protect_th2, allu - _PROTECT_OFF, alld)
    return _sorted_prefix(L, key, alld, alli, alle, allu)[1:]


# ---------------------------------------------------------------------------
# greedy (best-first) phase — paper Alg. 2 lines 5–28 + §4.1 early stopping
# ---------------------------------------------------------------------------

class GreedyState(NamedTuple):
    beam_dist: Array       # (B, L) ascending squared dists
    beam_idx: Array        # (B, L)
    beam_exp: Array        # (B, L) expanded flags
    visited: Array         # (B, W)
    best_dist: Array       # (B,)
    best_idx: Array        # (B,)
    since_improve: Array   # (B,)
    done: Array            # (B,)
    n_dist: Array          # (B,)
    n_esc: Array           # (B,) sketch8: candidates escalated to int8
    n_iters: Array         # ()
    n_slots: Array         # () candidate slots probed, masked included
    n_lane_iters: Array    # (B,) iterations in which the lane was active


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_data", "traverse_nondata"))
def greedy_search(index: GraphIndex, x: Array, seeds: Array,
                  seeds_valid: Array, theta: float | Array, *,
                  cfg: TraversalConfig, n_data: int,
                  traverse_nondata: bool = True,
                  cascade=None, qc=None) -> GreedyState:
    """Batched best-first search until an in-range point is found per lane.

    Args:
      x: (B, d) wave of queries; seeds: (B, S) start node ids.
      theta: threshold on the index metric's dissimilarity (scalar).
      cascade/qc: optional ``FilterCascade`` over the index vectors +
        queries encoded on its tiers' grids — traversal runs on certified
        lower bounds walked through the tier chain (see ``_probe``).
    """
    vecs, nbrs, metric = index.vecs, index.nbrs, index.metric
    B = x.shape[0]
    L, E = cfg.beam_width, cfg.expand_per_iter
    th2 = theta_key(theta, metric)
    W = bitmap_words(vecs.shape[0])
    visited0 = jnp.zeros((B, W), jnp.uint32)

    # --- seed probing (Alg. 2 lines 5–11) ---
    d0, _, v0, visited0, n0, e0 = _probe(
        vecs, x, seeds, seeds_valid, visited0, n_data=n_data,
        traverse_nondata=traverse_nondata, dist_impl=cfg.dist_impl,
        cascade=cascade, qc=qc, esc_th2=th2, metric=metric)
    bd = jnp.full((B, L), _INF)
    bi = jnp.full((B, L), NO_NODE, jnp.int32)
    bexp = jnp.zeros((B, L), bool)
    bd, bi, bexp = _beam_merge(bd, bi, bexp, d0,
                               jnp.where(v0, seeds, NO_NODE),
                               jnp.zeros_like(v0))
    best0 = jnp.min(d0, axis=1)
    besti0 = jnp.where(jnp.isfinite(best0),
                       jnp.take_along_axis(
                           jnp.where(v0, seeds, NO_NODE),
                           jnp.argmin(d0, axis=1)[:, None], axis=1)[:, 0],
                       NO_NODE)
    found0 = best0 < th2
    state = GreedyState(
        beam_dist=bd, beam_idx=bi, beam_exp=bexp, visited=visited0,
        best_dist=best0, best_idx=besti0,
        since_improve=jnp.zeros((B,), jnp.int32),
        done=found0, n_dist=n0, n_esc=e0, n_iters=jnp.int32(0),
        n_slots=jnp.int32(seeds.size),
        n_lane_iters=jnp.zeros((B,), jnp.int32))

    def cond(s: GreedyState):
        return (~jnp.all(s.done)) & (s.n_iters < cfg.max_iters)

    def body(s: GreedyState) -> GreedyState:
        active = ~s.done
        with jax.named_scope("select"):
            # pick top-E unexpanded beam entries (closest first)
            key = jnp.where((~s.beam_exp) & (s.beam_idx != NO_NODE)
                            & jnp.isfinite(s.beam_dist), -s.beam_dist,
                            -_INF)
            selk, selpos = jax.lax.top_k(key, E)            # (B, E)
            sel_valid = (selk > -_INF) & active[:, None]
            sel_ids = jnp.take_along_axis(s.beam_idx, selpos, axis=1)
            # mark them expanded (only where selected & active)
            lane = jnp.arange(B, dtype=jnp.int32)[:, None]
            new_exp = s.beam_exp.at[lane, selpos].max(sel_valid)
            exhausted = ~jnp.any(sel_valid, axis=1) & active

        cand, cd, _, cv, visited, n_new, n_esc = _expand(
            vecs, nbrs, x, sel_ids, sel_valid, s.visited, n_data=n_data,
            traverse_nondata=traverse_nondata, dist_impl=cfg.dist_impl,
            cascade=cascade, qc=qc, esc_th2=th2, metric=metric)
        visited = jnp.where(active[:, None], visited, s.visited)
        n_dist = s.n_dist + jnp.where(active, n_new, 0)
        n_esc2 = s.n_esc + jnp.where(active, n_esc, 0)

        with jax.named_scope("merge"):
            bd2, bi2, be2 = _beam_merge(
                s.beam_dist, s.beam_idx, new_exp, cd,
                jnp.where(cv, cand, NO_NODE), jnp.zeros_like(cv))
            bd2 = jnp.where(active[:, None], bd2, s.beam_dist)
            bi2 = jnp.where(active[:, None], bi2, s.beam_idx)
            be2 = jnp.where(active[:, None], be2, s.beam_exp)

        cbest = jnp.min(cd, axis=1)
        improved = cbest < s.best_dist
        best_dist = jnp.where(active & improved, cbest, s.best_dist)
        cbesti = jnp.take_along_axis(
            jnp.where(cv, cand, NO_NODE),
            jnp.argmin(cd, axis=1)[:, None], axis=1)[:, 0]
        best_idx = jnp.where(active & improved, cbesti, s.best_idx)
        since = jnp.where(active,
                          jnp.where(improved, 0, s.since_improve + 1),
                          s.since_improve)

        found = best_dist < th2
        plateau = (since >= cfg.patience) if cfg.patience >= 0 else jnp.zeros(
            (B,), bool)
        done = s.done | found | plateau | exhausted
        return GreedyState(bd2, bi2, be2, visited, best_dist, best_idx,
                           since, done, n_dist, n_esc2, s.n_iters + 1,
                           s.n_slots + cand.size,
                           s.n_lane_iters + active.astype(jnp.int32))

    return jax.lax.while_loop(cond, body, state)


# ---------------------------------------------------------------------------
# range expansion — BFS (Alg. 2 lines 29–42) / hybrid BBFS (Alg. 4)
# ---------------------------------------------------------------------------

class ExpandResult(NamedTuple):
    pool_idx: Array        # (B, C) in-range data node ids (NO_NODE padded)
    pool_dist: Array       # (B, C)
    n_pool: Array          # (B,)
    overflow: Array        # (B,) in-range hits beyond pool capacity
    best_dist: Array       # (B,) closest node seen overall (incl. greedy)
    best_idx: Array        # (B,)
    n_dist: Array          # (B,)
    n_esc: Array           # (B,) sketch8: escalations (incl. greedy's)
    n_iters: Array         # ()
    visited: Array         # (B, W)
    n_slots: Array         # () candidate slots probed (incl. the init's)
    n_lane_iters: Array    # (B,) active iterations (incl. greedy's)


class _ExpState(NamedTuple):
    pool_idx: Array
    pool_dist: Array
    pool_exp: Array        # (B, C+1) expanded flags (slot C = overflow sink)
    n_pool: Array
    overflow: Array
    hb_dist: Array         # (B, Lh) hybrid out-range beam
    hb_idx: Array
    hb_exp: Array
    hb_ub: Array           # (B, Lh) certified upper bounds (eviction guard)
    visited: Array
    best_dist: Array
    best_idx: Array
    qmax_prev: Array       # (B,)
    stall: Array           # (B,)
    done: Array
    n_dist: Array
    n_esc: Array
    n_iters: Array
    n_slots: Array
    n_lane_iters: Array


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_data", "hybrid", "traverse_nondata"))
def range_expand(index: GraphIndex, x: Array, theta: float | Array, *,
                 cfg: TraversalConfig, n_data: int, hybrid: bool,
                 traverse_nondata: bool,
                 init_idx: Array, init_dist: Array, init_valid: Array,
                 visited: Array, best_dist: Array, best_idx: Array,
                 n_dist: Array, cascade=None, qc=None,
                 init_ub: Array | None = None,
                 n_esc: Array | None = None,
                 n_slots: Array | None = None,
                 n_lane_iters: Array | None = None) -> ExpandResult:
    """Enumerate all reachable in-range data points from initial candidates.

    ``init_*`` (B, K0) are already-visited candidates with known distances
    (the greedy beam, or for the merged index the probed neighbor row).
    In-range data entries seed the result pool; the rest seed the hybrid
    out-range beam (BBFS only — plain BFS drops them, paper Alg. 2 line 29).

    Under a ``cascade`` all distances are certified lower bounds, so the
    pool is a superset of the exact pool over the visited region; the
    caller must re-rank pooled entries with the exact kernel before
    emitting pairs. ``init_ub`` optionally supplies certified upper
    bounds for the initial candidates (from ``_probe``); the hybrid
    out-range beam carries (lb, ub) pairs so eviction can never drop a
    candidate whose certified upper bound beats the protection radius
    ``cfg.hybrid_guard · θ²`` (the OOD recall floor — see
    ``_hybrid_merge``). ``n_dist``/``n_esc``/``n_slots``/``n_lane_iters``
    carry on the counts of the probes that produced the initial
    candidates.
    """
    vecs, nbrs, metric = index.vecs, index.nbrs, index.metric
    B, K0 = init_idx.shape
    C, Lh, E = cfg.pool_cap, cfg.hybrid_beam, cfg.expand_per_iter
    th2 = theta_key(theta, metric)
    # eviction protection only matters when distances are bounds, and
    # only if the guard is enabled (cfg.hybrid_guard > 0)
    protect_th2 = (jnp.float32(cfg.hybrid_guard) * th2
                   if cascade is not None and cfg.hybrid_guard > 0
                   else None)
    if n_esc is None:
        n_esc = jnp.zeros((B,), jnp.int32)
    if init_ub is None:
        init_ub = jnp.full(init_dist.shape, _INF)
    if n_slots is None:
        n_slots = jnp.int32(0)
    if n_lane_iters is None:
        n_lane_iters = jnp.zeros((B,), jnp.int32)

    is_data = (init_idx >= 0) & (init_idx < n_data)
    inr = init_valid & is_data & (init_dist < th2)

    # --- scatter in-range entries into the pool (slot C = overflow sink) ---
    pool_idx = jnp.full((B, C + 1), NO_NODE, jnp.int32)
    pool_dist = jnp.full((B, C + 1), _INF)
    pos = jnp.cumsum(inr, axis=1) - 1
    pos = jnp.where(inr, jnp.minimum(pos, C), C)
    lane = jnp.arange(B, dtype=jnp.int32)[:, None]
    pool_idx = pool_idx.at[lane, pos].set(jnp.where(inr, init_idx, NO_NODE))
    pool_dist = pool_dist.at[lane, pos].set(jnp.where(inr, init_dist, _INF))
    pool_idx = pool_idx.at[:, C].set(NO_NODE)
    pool_dist = pool_dist.at[:, C].set(_INF)
    n_pool = jnp.minimum(jnp.sum(inr, axis=1), C).astype(jnp.int32)
    overflow0 = jnp.maximum(jnp.sum(inr, axis=1) - C, 0).astype(jnp.int32)

    # --- hybrid beam init: out-range / non-data initial candidates ---
    hb_dist = jnp.full((B, max(Lh, 1)), _INF)
    hb_idx = jnp.full((B, max(Lh, 1)), NO_NODE, jnp.int32)
    hb_exp = jnp.zeros((B, max(Lh, 1)), bool)
    hb_ub = jnp.full((B, max(Lh, 1)), _INF)
    if hybrid and Lh > 0:
        outr = init_valid & ~inr
        hb_dist, hb_idx, hb_exp, hb_ub = _hybrid_merge(
            hb_dist, hb_idx, hb_exp, hb_ub,
            jnp.where(outr, init_dist, _INF),
            jnp.where(outr, init_idx, NO_NODE),
            jnp.zeros_like(outr),
            jnp.where(outr, init_ub, _INF),
            protect_th2=protect_th2)

    state = _ExpState(
        pool_idx=pool_idx, pool_dist=pool_dist,
        pool_exp=jnp.zeros((B, C + 1), bool).at[:, C].set(True),
        n_pool=n_pool, overflow=overflow0,
        hb_dist=hb_dist, hb_idx=hb_idx, hb_exp=hb_exp, hb_ub=hb_ub,
        visited=visited, best_dist=best_dist, best_idx=best_idx,
        qmax_prev=jnp.full((B,), _INF), stall=jnp.zeros((B,), jnp.int32),
        done=jnp.zeros((B,), bool), n_dist=n_dist, n_esc=n_esc,
        n_iters=jnp.int32(0), n_slots=n_slots, n_lane_iters=n_lane_iters)

    def cond(s: _ExpState):
        return (~jnp.all(s.done)) & (s.n_iters < cfg.max_iters)

    def body(s: _ExpState) -> _ExpState:
        active = ~s.done
        with jax.named_scope("select"):
            # --- select up to E unexpanded entries: pool (in-range)
            # first ---
            pkey = jnp.where((~s.pool_exp) & (s.pool_idx != NO_NODE),
                             2e30 - s.pool_dist, -_INF)      # (B, C+1)
            if hybrid and Lh > 0:
                hkey = jnp.where((~s.hb_exp) & (s.hb_idx != NO_NODE)
                                 & jnp.isfinite(s.hb_dist), -s.hb_dist,
                                 -_INF)
                key = jnp.concatenate([pkey, hkey], axis=1)
            else:
                key = pkey
            selk, selpos = jax.lax.top_k(key, E)
            sel_valid = (selk > -_INF) & active[:, None]
            from_pool = selpos < (C + 1)
            pool_pos = jnp.where(from_pool, selpos, 0)
            hb_pos = jnp.where(from_pool, 0, selpos - (C + 1))
            sel_ids = jnp.where(
                from_pool,
                jnp.take_along_axis(s.pool_idx, pool_pos, axis=1),
                jnp.take_along_axis(s.hb_idx, hb_pos, axis=1))
            lane2 = jnp.arange(B, dtype=jnp.int32)[:, None]
            pool_exp = s.pool_exp.at[lane2, pool_pos].max(
                sel_valid & from_pool)
            hb_exp2 = s.hb_exp.at[lane2, hb_pos].max(sel_valid & ~from_pool)
            any_inrange_unexp = jnp.any(
                (~pool_exp) & (s.pool_idx != NO_NODE), axis=1)
            exhausted = ~jnp.any(sel_valid, axis=1) & active

        cand, cd, cub, cv, visited, n_new, n_esc_new = _expand(
            vecs, nbrs, x, sel_ids, sel_valid, s.visited, n_data=n_data,
            traverse_nondata=traverse_nondata, dist_impl=cfg.dist_impl,
            cascade=cascade, qc=qc, esc_th2=th2, metric=metric)
        visited = jnp.where(active[:, None], visited, s.visited)
        n_dist2 = s.n_dist + jnp.where(active, n_new, 0)
        n_esc2 = s.n_esc + jnp.where(active, n_esc_new, 0)

        cis_data = (cand >= 0) & (cand < n_data)
        cinr = cv & cis_data & (cd < th2) & active[:, None]

        with jax.named_scope("merge"):
            # --- append in-range hits to the pool ---
            cpos = s.n_pool[:, None] + jnp.cumsum(cinr, axis=1) - 1
            cpos = jnp.where(cinr, jnp.minimum(cpos, C), C)
            pool_idx2 = s.pool_idx.at[lane2, cpos].set(
                jnp.where(cinr, cand, NO_NODE))
            pool_dist2 = s.pool_dist.at[lane2, cpos].set(
                jnp.where(cinr, cd, _INF))
            pool_idx2 = pool_idx2.at[:, C].set(NO_NODE)
            pool_dist2 = pool_dist2.at[:, C].set(_INF)
            pool_exp = pool_exp.at[:, C].set(True)
            n_hits = jnp.sum(cinr, axis=1).astype(jnp.int32)
            n_pool2 = jnp.minimum(s.n_pool + n_hits, C)
            overflow2 = s.overflow + jnp.maximum(
                s.n_pool + n_hits - C, 0) - jnp.maximum(s.n_pool - C, 0)

            # --- hybrid beam absorbs the rest (bounded, Alg. 4 lines
            # 12–16) ---
            if hybrid and Lh > 0:
                cout = cv & ~cinr & active[:, None]
                hb_dist2, hb_idx2, hb_exp3, hb_ub2 = _hybrid_merge(
                    s.hb_dist, s.hb_idx, hb_exp2, s.hb_ub,
                    jnp.where(cout, cd, _INF),
                    jnp.where(cout, cand, NO_NODE),
                    jnp.zeros_like(cout),
                    jnp.where(cout, cub, _INF),
                    protect_th2=protect_th2)
            else:
                hb_dist2, hb_idx2, hb_exp3, hb_ub2 = (
                    s.hb_dist, s.hb_idx, hb_exp2, s.hb_ub)

        # --- best-seen tracking (Alg. 2 lines 38–39; feeds SWS cache) ---
        cbest = jnp.min(cd, axis=1)
        improved = cbest < s.best_dist
        best_dist2 = jnp.where(active & improved, cbest, s.best_dist)
        cbesti = jnp.take_along_axis(
            jnp.where(cv, cand, NO_NODE),
            jnp.argmin(cd, axis=1)[:, None], axis=1)[:, 0]
        best_idx2 = jnp.where(active & improved, cbesti, s.best_idx)

        # --- termination ---
        if hybrid and Lh > 0:
            # max over *unexpanded* queue entries (paper: Q holds unexplored
            # candidates; the max only drops when closer arrivals evict the
            # back of a full queue — Alg. 4 lines 14–16).
            qmax = jnp.max(jnp.where((hb_idx2 != NO_NODE) & ~hb_exp3,
                                     hb_dist2, -_INF), axis=1)
            no_inr = ~(any_inrange_unexp | (n_hits > 0))
            decreased = qmax < s.qmax_prev
            stall2 = jnp.where(active,
                               jnp.where(no_inr & ~decreased, s.stall + 1, 0),
                               s.stall)
            done2 = s.done | exhausted | (
                (stall2 >= cfg.hybrid_patience) & no_inr)
            qmax_prev2 = jnp.where(active, qmax, s.qmax_prev)
        else:
            stall2 = s.stall
            qmax_prev2 = s.qmax_prev
            done2 = s.done | exhausted | (
                ~(any_inrange_unexp | (n_hits > 0)) & active)

        sel_changed = jnp.any(sel_valid, axis=1)
        keep = active & sel_changed
        pool_idx2 = jnp.where(keep[:, None], pool_idx2, s.pool_idx)
        pool_dist2 = jnp.where(keep[:, None], pool_dist2, s.pool_dist)

        return _ExpState(pool_idx2, pool_dist2, pool_exp,
                         jnp.where(keep, n_pool2, s.n_pool),
                         jnp.where(keep, overflow2, s.overflow),
                         hb_dist2, hb_idx2, hb_exp3, hb_ub2, visited,
                         best_dist2, best_idx2, qmax_prev2, stall2, done2,
                         n_dist2, n_esc2, s.n_iters + 1,
                         s.n_slots + cand.size,
                         s.n_lane_iters + active.astype(jnp.int32))

    fin = jax.lax.while_loop(cond, body, state)
    return ExpandResult(
        pool_idx=fin.pool_idx[:, :C], pool_dist=fin.pool_dist[:, :C],
        n_pool=fin.n_pool, overflow=fin.overflow,
        best_dist=fin.best_dist, best_idx=fin.best_idx,
        n_dist=fin.n_dist, n_esc=fin.n_esc, n_iters=fin.n_iters,
        visited=fin.visited, n_slots=fin.n_slots,
        n_lane_iters=fin.n_lane_iters)
