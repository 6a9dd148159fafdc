"""Wave runners — the online traversal phase of every join method.

Queries are processed in *waves* (DESIGN §2.4): MST wavefronts for the
work-sharing methods (parents always complete before children), arbitrary
chunks otherwise. Lanes beyond a short final wave are padded with invalid
seeds and masked throughout.

Since PR 5 the wave loop is a **two-stage software pipeline** (HARMONY's
overlapped-serving lever, arXiv:2506.14707). Each wave is split into

  * a *device phase* — greedy search, range expansion, and the
    band-compacted exact re-rank, dispatched asynchronously
    (``launch_search_wave`` / ``launch_mi_wave``); and
  * a *host phase* — the bulky pool transfer, pair assembly, and
    work-sharing cache update (``assemble_wave``).

The MST parent order makes wave *k+1* depend on wave *k*, but **only**
through the per-lane seed entries (the top-``seeds_max`` kept pool slots
for HWS, the single best node for SWS): those are computed device-side
and fetched as a small *seed-feedback* transfer (``fetch_feedback``), so
wave *k+1*'s traversal can be dispatched immediately while the host
assembles wave *k* in the shadow of the device. With ``overlap`` off
(``JoinConfig.overlap`` / the ``REPRO_OVERLAP`` env override) the same
primitives run strictly sequentially; pair sets and cache contents are
identical either way.

The exact re-rank runs on device through a band compaction
(``kernels.ops.band_compact``): the cascade's ambiguous band is stably
compacted into a small fixed capacity and only those rows reach the
scalar-prefetch ``gather_sq_dists`` kernel — f32 re-rank traffic scales
with band occupancy (PDX's pruning-proportional byte traffic,
arXiv:2503.04422), not with ``pool_cap``. Waves whose band overflows the
capacity are transparently retried at the next power of two
(``RerankCap``), so results never depend on the cap.

This module is the shared substrate of both entry points:

  * ``run_search_join`` / ``run_mi_join`` — one-shot full-batch joins
    (what ``vector_join`` and ``JoinEngine.join`` execute);
  * ``run_search_wave`` — a single padded wave with caller-supplied seeds
    (launch + fetch + assemble, sequentially), kept for callers that
    manage their own pipeline like ``JoinEngine.submit``.

All functions mutate the ``JoinStats`` they are handed and append
``(query_id, data_id)`` int64 pair blocks to ``all_pairs``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ordering, traversal
from repro.core.ood import predict_ood
from repro.core.types import (NO_NODE, GraphIndex, JoinConfig, JoinStats,
                              TraversalConfig, early_exit_enabled, env_flag,
                              theta_key)
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

Array = jax.Array
_INF = jnp.float32(jnp.inf)


def overlap_enabled(cfg: JoinConfig) -> bool:
    """``cfg.overlap``, unless the ``REPRO_OVERLAP`` env var overrides it
    (CI bisection: ``REPRO_OVERLAP=off`` forces the sequential path
    everywhere without touching configs; ``core.types.env_flag`` owns
    the empty-counts-as-unset grammar)."""
    return env_flag("REPRO_OVERLAP", cfg.overlap)


# single owner of the capacity-growth policy, shared with the sharded
# driver's retry (core/distributed.py)
next_pow2 = ops.next_pow2


class StickyCap:
    """Sticky power-of-two grow-and-retry capacity.

    The one overflow-retry shape used wherever a sparse set is compacted
    into a fixed-width device buffer: starts at ``init`` (rounded up to
    a power of two, clamped to ``limit``); a wave that overflows grows
    the capacity to the next power of two covering the observed
    occupancy and is retried. Powers of two keep the set of jit
    specializations tiny while the capacity tracks the high-water
    occupancy. Shared by the re-rank band (``RerankCap``) and the
    sharded driver's on-device pair-pool merge
    (``core.distributed.distributed_mi_join``).
    """

    def __init__(self, init: int, limit: int):
        self.limit = limit
        self.cap = min(next_pow2(max(init, 1)), limit)

    def grow(self, needed: int) -> None:
        self.cap = ops.grow_cap(self.cap, needed, self.limit)


class RerankCap(StickyCap):
    """``StickyCap`` for the ambiguous-band re-rank of one runner
    invocation, sized from the traversal config.

    ``init_cap`` overrides the config's cold-start value with a measured
    estimate (``JoinEngine.estimate_rerank_cap``'s LSH sample) without
    touching ``tcfg`` itself — ``TraversalConfig`` is a static jit
    argument, so threading the estimate through the config would
    recompile the traversal instead of just selecting a band capacity.
    """

    def __init__(self, tcfg: TraversalConfig, init_cap: int | None = None):
        init = (init_cap if init_cap is not None and init_cap > 0
                else tcfg.rerank_cap if tcfg.rerank_cap > 0
                else tcfg.pool_cap)
        super().__init__(init, tcfg.pool_cap)


# ---------------------------------------------------------------------------
# padding / assembly helpers
# ---------------------------------------------------------------------------

def pad_wave(ids: np.ndarray, wave_size: int) -> tuple[np.ndarray, np.ndarray]:
    n = ids.shape[0]
    if n == wave_size:
        return ids, np.ones(n, bool)
    pad = np.zeros(wave_size - n, ids.dtype)
    return np.concatenate([ids, pad]), np.concatenate(
        [np.ones(n, bool), np.zeros(wave_size - n, bool)])


def pool_mask(lane_valid: np.ndarray, n_pool: np.ndarray,
              C: int) -> np.ndarray:
    """(B, C) bool — which pool slots hold results (first-n layout)."""
    n_pool = np.where(lane_valid, n_pool, 0)
    return np.arange(C)[None, :] < n_pool[:, None]


def collect_pairs(qids: np.ndarray, keep: np.ndarray,
                  pool_idx: np.ndarray) -> np.ndarray:
    """Pairs from every kept pool slot; ``keep`` is a (B, C) bool mask
    (``pool_mask`` for the f32 path, post-rerank survivors for sq8)."""
    lanes, slots = np.nonzero(keep)
    return np.stack([qids[lanes], pool_idx[lanes, slots]], axis=1).astype(
        np.int64)


# ---------------------------------------------------------------------------
# device-side wave epilogue: band-compacted re-rank + seed feedback
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cap", "dist_impl", "seed_mode",
                                             "seeds_max", "early_exit"))
def _finalize_wave(cascade, qc, vecs, xw, pool_idx, pool_dist, n_pool,
                   lane_valid, best_idx, th2, *, cap: int,
                   dist_impl: str | None, seed_mode: str, seeds_max: int,
                   early_exit: bool = False):
    """Device epilogue of one wave: split the pooled lower-bound
    survivors into certified-sure vs ambiguous, re-rank only the
    band-compacted ambiguous entries with the exact scalar-prefetch
    gather kernel, and derive the seed-feedback arrays the next wave
    needs — all without a host round-trip.

    Replaces the old host-side ``rerank_pool``'s four-plus transfers
    (sure/amb masks down, ids back up, exact dists down) with device
    arrays the caller fetches in one fused ``device_get``.

    Cascades with a PDX tier route the band through the dimension-
    partitioned gather kernel instead of the full-``d`` f32 gather: the
    re-rank accumulates slab by slab over the store's PDX mirror and —
    with ``early_exit`` — retires lanes whose partial sum plus certified
    tail bound already exceeds θ². A retired lane reads +inf, but its
    full sum is certified ≥ θ², so ``keep`` (and, via the ``exact < th2``
    dist rule below, ``dist``) are identical on/off.

    Returns ``(keep, dist, n_amb, seed_ids, seed_valid, n_dims_scanned,
    n_dims_total)``:
      * ``keep``   (B, C) — emitted slots (post-rerank survivors);
      * ``dist``   (B, C) — exact where re-ranked, the certified lower
        bound on certified-sure slots, +inf elsewhere;
      * ``n_amb``  (B,)   — ambiguous-band occupancy per lane (band
        entries with rank ≥ ``cap`` were NOT re-ranked: the caller must
        retry at a larger cap whenever ``n_amb > cap``);
      * ``seed_ids`` / ``seed_valid`` (B, S) — per-lane seed feedback:
        the kept pool slots in ascending (dist, id) order for
        ``es_hws``, the single best node for ``es_sws``, empty
        otherwise. The (dist, id) key makes the order total, so the
        device sort and the host cache (``update_sws_cache``) agree
        bit-for-bit;
      * ``n_dims_scanned`` / ``n_dims_total`` () int32 — PDX re-rank
        dimension-scan counters (zero without a PDX tier).
    """
    B, C = pool_idx.shape
    keep = (jnp.arange(C)[None, :] < n_pool[:, None]) & lane_valid[:, None]
    dist = pool_dist
    n_amb = jnp.zeros((B,), jnp.int32)
    n_dims_scanned = jnp.zeros((), jnp.int32)
    n_dims_total = jnp.zeros((), jnp.int32)
    if cascade is not None:
        sure, amb = cascade.pool_band(qc, pool_dist, pool_idx, th2)
        sure = keep & sure
        amb = keep & amb
        pdx = cascade.tier("pdx")
        if pdx is not None:
            st = pdx.store
            qcp = qc[cascade.names.index("pdx")]
            (exact, within, n_amb, n_dims_scanned,
             n_dims_total) = ops.pdx_compact_gather_sq_dists(
                st.vp, st.ftail, st.ftail[:, 0], qcp.vp, qcp.ftail,
                qcp.ftail[:, 0], pool_idx, amb, min(cap, C), th2,
                dim=st.dim, early_exit=early_exit, impl=dist_impl)
            keep = sure | (within & (exact < th2))
            # exact < th2 (not isfinite): an early-exited slot reads +inf
            # here but a finite certified-out value with exit off — both
            # fall back to pool_dist, keeping seed feedback identical.
            dist = jnp.where(within & (exact < th2), exact, pool_dist)
        else:
            exact, within, n_amb = ops.compact_gather_sq_dists(
                vecs, xw, pool_idx, amb, min(cap, C), impl=dist_impl)
            keep = sure | (within & (exact < th2))
            dist = jnp.where(within & jnp.isfinite(exact), exact, pool_dist)
    dist = jnp.where(keep, dist, _INF)
    if seed_mode == "es_hws":
        S = min(seeds_max, C)
        sd, si = jax.lax.sort((jnp.where(keep, dist, _INF), pool_idx),
                              dimension=1, num_keys=2, is_stable=True)
        seed_ids, seed_valid = si[:, :S], jnp.isfinite(sd[:, :S])
    elif seed_mode == "es_sws":
        seed_ids = best_idx[:, None].astype(jnp.int32)
        seed_valid = (best_idx != NO_NODE)[:, None] & lane_valid[:, None]
    else:
        seed_ids = jnp.zeros((B, 0), jnp.int32)
        seed_valid = jnp.zeros((B, 0), bool)
    return (keep, dist, n_amb, seed_ids, seed_valid, n_dims_scanned,
            n_dims_total)


@dataclasses.dataclass
class WaveHandles:
    """One in-flight wave: device handles plus everything needed to
    retry the re-rank epilogue at a larger band capacity."""
    qids: np.ndarray               # (B,) global query ids
    lane_valid: np.ndarray         # (B,) bool
    xw: Array                      # (B, d) wave queries (device)
    vecs: Array                    # index vector table (device)
    cascade: object                # FilterCascade | None
    qc: tuple | None
    th2: Array
    # raw traversal outputs (kept for the retry path)
    pool_idx: Array
    raw_pool_dist: Array
    n_pool: Array
    best_idx: Array
    n_dist: Array
    n_esc: Array
    overflow: Array
    n_iters: tuple                 # device scalars, summed at assembly
    n_slots: Array                 # () candidate slots probed
    n_lane_iters: Array            # (B,) active iterations per lane
    hybrid: bool                   # range expansion on the BBFS path
    # epilogue outputs (replaced wholesale on a capacity retry)
    keep: Array
    dist: Array
    n_amb: Array
    seed_ids: Array
    seed_valid: Array
    n_dims_scanned: Array          # () int32 — PDX re-rank scan counters
    n_dims_total: Array
    # epilogue parameters
    capctl: RerankCap
    dist_impl: str | None
    seed_mode: str
    seeds_max: int
    early_exit: bool = False
    wave: int = 0                  # index of the wave in its call (spans)
    # host-side state filled by the feedback fetch
    n_amb_host: np.ndarray | None = None
    tombstones: list = dataclasses.field(default_factory=list)


def _refinalize(h: WaveHandles, stats: JoinStats) -> None:
    """Re-run the device epilogue at the (grown) capacity."""
    with obs_trace.span("wave/refinalize", wave=h.wave, cap=h.capctl.cap):
        (h.keep, h.dist, h.n_amb, h.seed_ids, h.seed_valid, h.n_dims_scanned,
         h.n_dims_total) = _finalize_wave(
            h.cascade, h.qc, h.vecs, h.xw, h.pool_idx, h.raw_pool_dist,
            h.n_pool, jnp.asarray(h.lane_valid), h.best_idx, h.th2,
            cap=h.capctl.cap, dist_impl=h.dist_impl, seed_mode=h.seed_mode,
            seeds_max=h.seeds_max, early_exit=h.early_exit)
    if h.cascade is not None:
        stats.n_rerank_gather += int(h.xw.shape[0]) * h.capctl.cap
        stats.bytes_band += (int(h.xw.shape[0]) * h.capctl.cap
                             * int(h.xw.shape[1]) * 4)


def _resolve_band(h: WaveHandles, stats: JoinStats) -> None:
    """Fetch the per-lane band occupancy; if any lane's band overflowed
    the compaction capacity, grow the cap and re-run the epilogue so the
    emitted set never depends on the capacity choice."""
    if h.n_amb_host is not None:
        return
    t0 = time.perf_counter()
    with obs_trace.span("wave/band", wave=h.wave) as sp:
        with obs_trace.span("wave/fetch", wave=h.wave):
            n_amb = np.asarray(jax.device_get(h.n_amb))
        max_amb = int(n_amb.max()) if n_amb.size else 0
        if h.cascade is not None and max_amb > h.capctl.cap:
            stats.overflow_retries += 1
            h.capctl.grow(max_amb)
            _refinalize(h, stats)
            with obs_trace.span("wave/fetch", wave=h.wave):
                n_amb = np.asarray(jax.device_get(h.n_amb))
        if obs_trace.recording():
            sp.set_metadata(band_occ=max_amb, cap=h.capctl.cap)
    h.n_amb_host = n_amb
    stats.wait_seconds += time.perf_counter() - t0
    stats.bytes_feedback += n_amb.nbytes
    obs_metrics.metrics().histogram(
        "wave.band_occ", help="per-wave max ambiguous-band occupancy"
    ).observe(max_amb)


def fetch_feedback(h: WaveHandles, stats: JoinStats) -> dict[int, np.ndarray]:
    """The small blocking transfer between waves: band occupancy (for the
    capacity-overflow retry) plus the per-lane seed entries. Returns the
    seed-cache overlay ``{qid: ids}`` — for a caching method these are
    exactly the first ``seeds_max`` ids ``update_sws_cache`` will later
    store for the same queries, so the next wave can seed from them
    before the bulky pool ever reaches the host."""
    _resolve_band(h, stats)
    if h.seed_mode == "none":
        return {}
    t0 = time.perf_counter()
    with obs_trace.span("wave/feedback", wave=h.wave), \
            obs_trace.span("wave/fetch", wave=h.wave):
        seed_ids, seed_valid = jax.device_get((h.seed_ids, h.seed_valid))
    stats.wait_seconds += time.perf_counter() - t0
    stats.bytes_feedback += seed_ids.nbytes + seed_valid.nbytes
    entries = {}
    for i, q in enumerate(h.qids):
        if h.lane_valid[i]:
            entries[int(q)] = np.asarray(seed_ids[i][seed_valid[i]],
                                         np.int32)
    return entries


def assemble_wave(h: WaveHandles, stats: JoinStats, *,
                  qid_offset: int = 0) -> "WaveOutput":
    """The host phase of one wave: one fused device→host transfer of the
    (idx, dist, keep, stats) block, then pair assembly. In a pipelined
    run this executes while the device traverses the next wave."""
    _resolve_band(h, stats)
    t0 = time.perf_counter()
    with obs_trace.span("wave/assemble", wave=h.wave) as sp:
        with obs_trace.span("wave/fetch", wave=h.wave):
            (pool_idx, pool_dist, keep, n_pool, best_idx, n_dist, n_esc,
             overflow, nds, ndt, n_slots, n_lane_iters,
             *iters) = jax.device_get(
                (h.pool_idx, h.dist, h.keep, h.n_pool, h.best_idx,
                 h.n_dist, h.n_esc, h.overflow, h.n_dims_scanned,
                 h.n_dims_total, h.n_slots, h.n_lane_iters) + h.n_iters)
        lv = h.lane_valid
        pairs = collect_pairs(h.qids + qid_offset, keep, pool_idx)
        stats.n_dist += int(n_dist[lv].sum())
        stats.n_esc8 += int(n_esc[lv].sum())
        stats.n_overflow += int(overflow[lv].sum())
        stats.n_rerank += int(h.n_amb_host[lv].sum())
        stats.n_dims_scanned += int(nds)
        stats.n_dims_total += int(ndt)
        stats.n_iters += sum(int(i) for i in iters)
        stats.n_slots += int(n_slots)
        lane_iters = int(n_lane_iters[lv].sum())
        stats.n_lane_iters += lane_iters
        if h.hybrid:
            stats.n_hybrid_lane_iters += lane_iters
        stats.bytes_assembly += (
            pool_idx.nbytes + pool_dist.nbytes + keep.nbytes + n_pool.nbytes
            + best_idx.nbytes + n_dist.nbytes + n_esc.nbytes
            + overflow.nbytes + n_lane_iters.nbytes)
        if obs_trace.recording():
            sp.set_metadata(pairs=int(pairs.shape[0]),
                            lanes=int(np.count_nonzero(lv)))
    stats.other_seconds += time.perf_counter() - t0
    obs_metrics.metrics().histogram(
        "wave.pairs", help="result pairs emitted per wave"
    ).observe(pairs.shape[0])
    return WaveOutput(pairs=pairs, pool_idx=np.asarray(pool_idx),
                      pool_dist=np.asarray(pool_dist),
                      pool_keep=np.asarray(keep),
                      n_pool=np.asarray(n_pool),
                      best_idx=np.asarray(best_idx), lane_valid=lv)


# ---------------------------------------------------------------------------
# MI seed probing (greedy phase offloaded to the index — paper §4.4)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("traverse_nondata", "dist_impl"))
def _mi_probe(merged: GraphIndex, x: Array, qids: Array, lane_valid: Array, *,
              traverse_nondata: bool, dist_impl: str | None,
              cascade=None, qc=None, esc_th2=None):
    """Probe each query's own neighborhood row in the merged index."""
    B = x.shape[0]
    W = traversal.bitmap_words(merged.n_nodes)
    visited = jnp.zeros((B, W), jnp.uint32)
    # mark the query's own node visited so traversal never loops back
    lane = jnp.arange(B, dtype=jnp.int32)
    visited = visited.at[lane, (qids >> 5)].add(
        jnp.uint32(1) << (qids & 31).astype(jnp.uint32))
    rows = merged.nbrs[qids]                                 # (B, R)
    valid = jnp.broadcast_to(lane_valid[:, None], rows.shape)
    dist, ub, valid, visited, n_new, n_esc = traversal._probe(
        merged.vecs, x, rows, valid, visited,
        n_data=merged.n_data, traverse_nondata=traverse_nondata,
        dist_impl=dist_impl, cascade=cascade, qc=qc, esc_th2=esc_th2,
        metric=merged.metric)
    best = jnp.min(dist, axis=1)
    besti = jnp.take_along_axis(
        jnp.where(valid, rows, NO_NODE),
        jnp.argmin(dist, axis=1)[:, None], axis=1)[:, 0]
    return rows, dist, ub, valid, visited, n_new, n_esc, best, besti


# ---------------------------------------------------------------------------
# search-path waves (index / es / es_hws / es_sws)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WaveOutput:
    """Everything a caller needs to both assemble pairs and feed the
    work-sharing cache after one wave."""
    pairs: np.ndarray          # (P, 2) int64, already offset to global qids
    pool_idx: np.ndarray       # (B, C) int32
    pool_dist: np.ndarray      # (B, C) f32 (sq8: exact where re-ranked,
    #                            certified lower bound on sure slots)
    pool_keep: np.ndarray      # (B, C) bool — emitted slots (post-rerank)
    n_pool: np.ndarray         # (B,)  int32 (pre-rerank pool fill)
    best_idx: np.ndarray       # (B,)  int32 — closest data node per lane
    lane_valid: np.ndarray     # (B,)  bool


def effective_tcfg(cfg: JoinConfig) -> TraversalConfig:
    """The INDEX baseline is ES with early stopping disabled."""
    tcfg = cfg.traversal
    if cfg.method == "index" and tcfg.patience >= 0:
        tcfg = dataclasses.replace(tcfg, patience=-1)
    return tcfg


def launch_search_wave(index_y: GraphIndex, xw: Array, qids: np.ndarray,
                       lane_valid: np.ndarray, cfg: JoinConfig,
                       stats: JoinStats, *, seeds: np.ndarray,
                       seeds_valid: np.ndarray, cascade=None, qc=None,
                       capctl: RerankCap | None = None, sync: bool = True,
                       collect_seeds: bool = False,
                       wave: int = 0) -> WaveHandles:
    """Dispatch the device phase of one search wave (Alg. 1 online):
    greedy search, range expansion, and the band-compacted re-rank +
    seed-feedback epilogue. With ``sync`` the greedy/expand phases are
    timed individually (the sequential path); otherwise nothing blocks —
    the caller overlaps ``assemble_wave`` of the previous wave with this
    wave's device execution.

    ``seeds``/``seeds_valid`` are (B, S) arrays the caller filled from
    whatever work-sharing cache applies (parent caches for the MST order,
    the streaming carry cache for ``JoinEngine.submit``).

    With a ``cascade`` the traversal filters on certified lower bounds
    walked through the tier chain and the pooled survivors are re-ranked
    with the exact f32 kernel before pairs are emitted (per-tier
    escalation counts land in ``stats.n_dist`` / ``stats.n_esc8``).
    ``qc`` optionally supplies queries already encoded on the cascade's
    grids (the streaming path encodes once per wave and reuses the codes
    for parent assignment). ``wave`` is the wave's index in its call,
    for the spans.
    """
    tcfg = effective_tcfg(cfg)
    if capctl is None:
        capctl = RerankCap(tcfg)
    with obs_trace.span("wave/launch", wave=wave):
        seeds_j = jnp.asarray(seeds)
        sv_j = jnp.asarray(seeds_valid) & jnp.asarray(lane_valid)[:, None]
        if cascade is not None and qc is None:
            qc = cascade.encode(xw)
        th2 = theta_key(cfg.theta, index_y.metric)

        t0 = time.perf_counter()
        g = traversal.greedy_search(
            index_y, xw, seeds_j, sv_j, cfg.theta, cfg=tcfg,
            n_data=index_y.n_data, traverse_nondata=True,
            cascade=cascade, qc=qc)
        if sync:
            jax.block_until_ready(g.beam_dist)
            stats.greedy_seconds += time.perf_counter() - t0
            t0 = time.perf_counter()

        init_valid = (g.beam_idx != NO_NODE) & jnp.isfinite(g.beam_dist)
        r = traversal.range_expand(
            index_y, xw, cfg.theta, cfg=tcfg, n_data=index_y.n_data,
            hybrid=False, traverse_nondata=True,
            init_idx=g.beam_idx, init_dist=g.beam_dist, init_valid=init_valid,
            visited=g.visited, best_dist=g.best_dist, best_idx=g.best_idx,
            n_dist=g.n_dist, cascade=cascade, qc=qc, n_esc=g.n_esc,
            n_slots=g.n_slots, n_lane_iters=g.n_lane_iters)
        if sync:
            jax.block_until_ready(r.pool_idx)
            stats.expand_seconds += time.perf_counter() - t0

        seed_mode = cfg.method if collect_seeds else "none"
        ee = early_exit_enabled(tcfg)
        keep, dist, n_amb, seed_ids, seed_valid2, nds, ndt = _finalize_wave(
            cascade, qc, index_y.vecs, xw, r.pool_idx, r.pool_dist, r.n_pool,
            jnp.asarray(lane_valid), r.best_idx, th2, cap=capctl.cap,
            dist_impl=tcfg.dist_impl, seed_mode=seed_mode,
            seeds_max=tcfg.seeds_max, early_exit=ee)
        if cascade is not None:
            stats.n_rerank_gather += int(xw.shape[0]) * capctl.cap
            stats.bytes_band += (int(xw.shape[0]) * capctl.cap
                                 * int(xw.shape[1]) * 4)
        return WaveHandles(
            qids=qids, lane_valid=np.asarray(lane_valid), xw=xw,
            vecs=index_y.vecs, cascade=cascade, qc=qc, th2=th2,
            pool_idx=r.pool_idx, raw_pool_dist=r.pool_dist, n_pool=r.n_pool,
            best_idx=r.best_idx, n_dist=r.n_dist, n_esc=r.n_esc,
            overflow=r.overflow, n_iters=(g.n_iters, r.n_iters),
            n_slots=r.n_slots, n_lane_iters=r.n_lane_iters, hybrid=False,
            keep=keep, dist=dist, n_amb=n_amb, seed_ids=seed_ids,
            seed_valid=seed_valid2, n_dims_scanned=nds, n_dims_total=ndt,
            capctl=capctl, dist_impl=tcfg.dist_impl,
            seed_mode=seed_mode, seeds_max=tcfg.seeds_max, early_exit=ee,
            wave=wave)


def run_search_wave(index_y: GraphIndex, xw: Array, qids: np.ndarray,
                    lane_valid: np.ndarray, cfg: JoinConfig,
                    stats: JoinStats, *, seeds: np.ndarray,
                    seeds_valid: np.ndarray,
                    cascade=None, qc=None) -> WaveOutput:
    """One padded wave, strictly sequential (launch + fetch + assemble) —
    the single-wave convenience the pipelined runners are built from."""
    h = launch_search_wave(index_y, xw, qids, lane_valid, cfg, stats,
                           seeds=seeds, seeds_valid=seeds_valid,
                           cascade=cascade, qc=qc, sync=True)
    return assemble_wave(h, stats)


def update_sws_cache(cache: dict[int, np.ndarray], out: WaveOutput,
                     qids: np.ndarray, cfg: JoinConfig,
                     stats: JoinStats, cache_n: int) -> int:
    """SelectDataToCache (Alg. 3) — HWS caches the whole in-range pool,
    SWS the single closest node. Returns the updated entry count.

    HWS entries are ordered by the total (dist, id) key — the same key
    the device-side seed feedback sorts by, so a pipelined wave seeds
    from exactly the prefix of the entry this writes."""
    if cfg.method == "es_hws":
        for i, q in enumerate(qids):
            if not out.lane_valid[i]:
                continue
            old = cache.get(int(q))
            if old is not None:          # overwrite evicts the old entry
                stats.cache_evictions += 1
                cache_n -= int(old.size)
            ids = out.pool_idx[i][out.pool_keep[i]]
            o = np.lexsort((ids, out.pool_dist[i][out.pool_keep[i]]))
            cache[int(q)] = ids[o]
            cache_n += int(ids.size)
    elif cfg.method == "es_sws":
        for i, q in enumerate(qids):
            if not out.lane_valid[i]:
                continue
            if int(q) in cache:
                stats.cache_evictions += 1
                cache_n -= 1
            b = int(out.best_idx[i])
            cache[int(q)] = (np.asarray([b], np.int32) if b != NO_NODE
                             else np.empty(0, np.int32))
            cache_n += 1
    stats.peak_cache_entries = max(stats.peak_cache_entries, cache_n)
    return cache_n


def seeds_from_cache(qids: np.ndarray, lane_valid: np.ndarray,
                     parent: np.ndarray | dict[int, int],
                     cache, sy: int,
                     wave_size: int, seeds_max: int,
                     stats: JoinStats | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Seed lanes from parent caches (Alg. 1 lines 5–9); s_Y fallback.

    ``cache`` is any mapping qid → id array — the pipelined runners pass
    a ``ChainMap(seed_overlay, cache)`` so a wave can seed from the
    feedback of the still-being-assembled previous wave.

    With ``stats`` every lane that has a parent counts as a cache hit
    (a usable non-empty entry) or miss (the lane fell back to s_Y) —
    the work-sharing effectiveness rate of the paper's core claim.
    """
    seeds = np.full((wave_size, seeds_max), sy, np.int32)
    seeds_valid = np.zeros((wave_size, seeds_max), bool)
    seeds_valid[:, 0] = True
    get = (parent.get if isinstance(parent, dict)
           else lambda q: int(parent[q]))
    for i, q in enumerate(qids):
        p = get(int(q)) if lane_valid[i] else -1
        p = -1 if p is None else int(p)
        if p < 0:
            continue
        c = cache.get(p)
        if c is not None and c.size > 0:
            k = min(seeds_max, c.size)
            seeds[i, :k] = c[:k]
            seeds_valid[i, :k] = True
            if stats is not None:
                stats.cache_hits += 1
        elif stats is not None:
            stats.cache_misses += 1
    return seeds, seeds_valid


def run_search_join(X: Array, index_y: GraphIndex,
                    index_x: GraphIndex | None, cfg: JoinConfig,
                    stats: JoinStats, all_pairs: list[np.ndarray], *,
                    cascade=None, capctl: RerankCap | None = None) -> None:
    """Full-batch index / es / es_hws / es_sws join (greedy + BFS).

    Pipelined (``overlap_enabled``): wave *k+1* launches from wave *k*'s
    seed feedback while wave *k*'s pool is still on the device; the host
    assembles pairs and the work-sharing cache one wave behind. The seed
    overlay is dropped as soon as ``update_sws_cache`` writes the full
    entry, so cache contents match the sequential path exactly.

    ``capctl`` seeds the band capacity from a measured estimate
    (``JoinEngine.estimate_rerank_cap``); overflow is still detected and
    retried, so the estimate is advisory-only for correctness.
    """
    nq = X.shape[0]
    needs_mst = cfg.method in ("es_hws", "es_sws")
    sy = int(index_y.start)

    t0 = time.perf_counter()
    if needs_mst:
        parent = ordering.mst_order(index_x, index_y.vecs[sy])
        waves = ordering.wavefronts(parent, cfg.wave_size)
    else:
        parent = np.full(nq, -1, np.int64)
        order = np.arange(nq)
        waves = [order[i:i + cfg.wave_size]
                 for i in range(0, nq, cfg.wave_size)]
    stats.other_seconds += time.perf_counter() - t0

    S = cfg.traversal.seeds_max
    cache: dict[int, np.ndarray] = {}
    cache_n = 0
    overlay: dict[int, np.ndarray] = {}
    seed_cache = collections.ChainMap(overlay, cache)
    if capctl is None:
        capctl = RerankCap(effective_tcfg(cfg))
    ov = overlap_enabled(cfg)
    pending: WaveHandles | None = None

    def drain(h: WaveHandles) -> None:
        nonlocal cache_n
        out = assemble_wave(h, stats)
        all_pairs.append(out.pairs)
        t1 = time.perf_counter()
        with obs_trace.span("wave/cache_update", wave=h.wave):
            cache_n = update_sws_cache(cache, out, h.qids, cfg, stats,
                                       cache_n)
            for q in h.qids[h.lane_valid]:
                overlay.pop(int(q), None)
        stats.other_seconds += time.perf_counter() - t1

    for k, wave in enumerate(waves):
        qids, lane_valid = pad_wave(wave, cfg.wave_size)
        xw = X[jnp.asarray(qids)]
        t0 = time.perf_counter()
        seeds, seeds_valid = seeds_from_cache(
            qids, lane_valid, parent, seed_cache, sy, cfg.wave_size, S,
            stats=stats)
        stats.other_seconds += time.perf_counter() - t0
        # the seed feedback only exists to bridge the one-wave gap the
        # pipeline opens; the sequential path updates the cache in full
        # before the next wave and needs neither the device sort nor the
        # extra fetch
        h = launch_search_wave(index_y, xw, qids, lane_valid, cfg, stats,
                               seeds=seeds, seeds_valid=seeds_valid,
                               cascade=cascade, capctl=capctl,
                               sync=not ov, collect_seeds=needs_mst and ov,
                               wave=k)
        if ov and pending is not None:
            drain(pending)
            pending = None
        if needs_mst and ov:
            overlay.update(fetch_feedback(h, stats))
        if ov:
            pending = h
        else:
            drain(h)
    if pending is not None:
        drain(pending)


# ---------------------------------------------------------------------------
# merged-index waves (es_mi / es_mi_adapt)
# ---------------------------------------------------------------------------

def launch_mi_wave(merged: GraphIndex, xw: Array, qids: np.ndarray,
                   lane_valid: np.ndarray, cfg: JoinConfig,
                   stats: JoinStats, *, hybrid: bool, cascade=None,
                   qc=None, capctl: RerankCap | None = None,
                   sync: bool = True, wave: int = 0) -> WaveHandles:
    """Dispatch the device phase of one merged-index wave (probe +
    BFS/BBFS expansion + band-compacted re-rank). MI waves carry no
    work-sharing cache, so there is no seed feedback — the pipeline
    overlaps the next wave with pure pair assembly."""
    with obs_trace.span("wave/launch", wave=wave):
        tcfg = cfg.traversal
        n_data = merged.n_data
        node_ids = jnp.asarray(qids, jnp.int32) + n_data
        lv_j = jnp.asarray(lane_valid)
        if cascade is not None and qc is None:
            qc = cascade.encode(xw)
        th2 = theta_key(cfg.theta, merged.metric)
        if capctl is None:
            capctl = RerankCap(tcfg)

        t0 = time.perf_counter()
        rows, dist, ub, valid, visited, n_new, n_esc0, best, besti = _mi_probe(
            merged, xw, node_ids, lv_j,
            traverse_nondata=hybrid, dist_impl=tcfg.dist_impl,
            cascade=cascade, qc=qc, esc_th2=th2)
        if sync:
            jax.block_until_ready(dist)
            stats.greedy_seconds += time.perf_counter() - t0
            t0 = time.perf_counter()

        r = traversal.range_expand(
            merged, xw, cfg.theta, cfg=tcfg, n_data=n_data,
            hybrid=hybrid, traverse_nondata=hybrid,
            init_idx=rows, init_dist=dist, init_valid=valid,
            visited=visited, best_dist=best, best_idx=besti,
            n_dist=n_new, cascade=cascade, qc=qc, init_ub=ub, n_esc=n_esc0,
            n_slots=jnp.int32(rows.size))
        if sync:
            jax.block_until_ready(r.pool_idx)
            stats.expand_seconds += time.perf_counter() - t0

        ee = early_exit_enabled(tcfg)
        keep, dist2, n_amb, seed_ids, seed_valid, nds, ndt = _finalize_wave(
            cascade, qc, merged.vecs, xw, r.pool_idx, r.pool_dist, r.n_pool,
            lv_j, r.best_idx, th2, cap=capctl.cap, dist_impl=tcfg.dist_impl,
            seed_mode="none", seeds_max=tcfg.seeds_max, early_exit=ee)
        if cascade is not None:
            stats.n_rerank_gather += int(xw.shape[0]) * capctl.cap
            stats.bytes_band += (int(xw.shape[0]) * capctl.cap
                                 * int(xw.shape[1]) * 4)
        return WaveHandles(
            qids=qids, lane_valid=np.asarray(lane_valid), xw=xw,
            vecs=merged.vecs, cascade=cascade, qc=qc, th2=th2,
            pool_idx=r.pool_idx, raw_pool_dist=r.pool_dist, n_pool=r.n_pool,
            best_idx=r.best_idx, n_dist=r.n_dist, n_esc=r.n_esc,
            overflow=r.overflow, n_iters=(r.n_iters,),
            n_slots=r.n_slots, n_lane_iters=r.n_lane_iters, hybrid=hybrid,
            keep=keep, dist=dist2, n_amb=n_amb, seed_ids=seed_ids,
            seed_valid=seed_valid, n_dims_scanned=nds, n_dims_total=ndt,
            capctl=capctl, dist_impl=tcfg.dist_impl,
            seed_mode="none", seeds_max=tcfg.seeds_max, early_exit=ee,
            wave=wave)


def run_mi_join(X: Array, merged: GraphIndex, cfg: JoinConfig,
                stats: JoinStats, all_pairs: list[np.ndarray], *,
                qid_offset: int = 0, cascade=None,
                capctl: RerankCap | None = None) -> None:
    """es_mi / es_mi_adapt join (greedy offloaded; BFS or adaptive BBFS).

    ``qid_offset`` shifts the emitted query ids — used by the streaming
    engine, where a batch of local queries carries global ids.
    ``cascade`` compresses the *merged* index (data + query nodes);
    pooled survivors are re-ranked exactly before emission. MI waves are
    mutually independent, so the pipeline double-buffers unconditionally
    (including across the BFS/BBFS group boundary).
    """
    nq = X.shape[0]
    n_data = merged.n_data

    # adaptive split: predict OOD once, vectorized (paper §4.5)
    t0 = time.perf_counter()
    if cfg.method == "es_mi_adapt":
        flags = []
        with obs_trace.span("join/ood"):
            for q0 in range(0, nq, 4096):
                q1 = min(q0 + 4096, nq)
                qid = n_data + jnp.arange(q0, q1, dtype=jnp.int32)
                flags.append(np.asarray(predict_ood(
                    merged, X[q0:q1], qid, factor=cfg.ood_factor)))
        ood = np.concatenate(flags)
        stats.n_ood = int(ood.sum())
    else:
        ood = np.zeros(nq, bool)
    groups = [(np.flatnonzero(~ood), False), (np.flatnonzero(ood), True)]
    stats.other_seconds += time.perf_counter() - t0

    if capctl is None:
        capctl = RerankCap(cfg.traversal)
    ov = overlap_enabled(cfg)
    pending: WaveHandles | None = None

    def drain(h: WaveHandles) -> None:
        out = assemble_wave(h, stats, qid_offset=qid_offset)
        all_pairs.append(out.pairs)

    k = 0
    for ids_all, hybrid in groups:
        for c0 in range(0, ids_all.size, cfg.wave_size):
            wave = ids_all[c0:c0 + cfg.wave_size]
            qids, lane_valid = pad_wave(wave, cfg.wave_size)
            xw = X[jnp.asarray(qids)]
            qc = cascade.encode(xw) if cascade is not None else None
            h = launch_mi_wave(merged, xw, qids, lane_valid, cfg, stats,
                               hybrid=hybrid, cascade=cascade, qc=qc,
                               capctl=capctl, sync=not ov, wave=k)
            k += 1
            if ov:
                if pending is not None:
                    drain(pending)
                pending = h
            else:
                drain(h)
    if pending is not None:
        drain(pending)
