"""JoinEngine — a persistent, sharded serving layer for threshold joins.

The paper's framework is a one-shot ``vector_join()`` call: every
invocation rebuilds its indexes and runs on one device. The engine turns
it into a long-lived service object (the substrate for the ROADMAP's
production north star):

  * **Index caching** — ``GraphIndex`` artifacts (data index, query index,
    merged index, per-shard merged indexes) are built once and reused
    across repeated joins, threshold sweeps, and method switches. Builds
    are counted in ``build_counts`` so callers (and tests) can assert
    reuse. Per-query-set artifacts are keyed by a content fingerprint of
    X and held in a small LRU.
  * **Streaming** — ``submit(X_batch)`` pads each incoming batch into
    waves and joins it against Y under *global* query ids. For the
    work-sharing methods the cache of completed queries is carried
    forward between batches: each new query seeds from the cache entry of
    the nearest already-completed query (the streaming analogue of the
    paper's MST parent order, where the MST cannot be known up front).
  * **Sharding** — with ``n_shards > 1`` the data side is partitioned
    across devices via ``shard_map`` (core/distributed.py): one merged
    subgraph per device, query waves replicated, per-shard in-range pools
    merged on the host. ``X ⋈_θ Y = ∪_s (X ⋈_θ Y_s)`` holds exactly, so
    recall composes additively across shards.

  * **Metric** — the engine's ``metric`` (``core.types.METRICS``) is the
    table's space, carried on every index artifact it builds
    (``GraphIndex.metric``): squared L2, or the inner product (``ip``, θ a
    threshold on −⟨x, y⟩). The certified bounds of the quant modes and
    the mesh driver assume L2, so ``ip`` runs with quant ``off`` on one
    device and raises a ``ValueError`` otherwise.

``vector_join()`` remains as a thin compatibility wrapper over a
transient engine.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from collections import ChainMap, OrderedDict
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (QUANT_FILTER_MODES, QUANT_MODES, GraphIndex,
                              JoinConfig, JoinResult, JoinStats, check_metric,
                              early_exit_enabled)
from repro.engine import waves as W
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

Array = jax.Array

_MI_METHODS = ("es_mi", "es_mi_adapt")
_SEARCH_METHODS = ("index", "es", "es_hws", "es_sws")
_CACHING_METHODS = ("es_hws", "es_sws")


# ~64 KiB of content sampled per fingerprint — enough that any two vector
# sets that differ anywhere but on a vanishing fraction of bytes get
# distinct keys, while keying stays O(sample) instead of O(N·d).
_FP_SAMPLE_BYTES = 1 << 16


def _fingerprint(a) -> str:
    """Content hash of a vector set — the cache key for per-X artifacts.

    Hashes shape/dtype/nbytes plus a fixed-size strided byte sample (head
    and tail included), so fingerprinting a multi-GB array costs the same
    as a small one. Sampling trades exhaustiveness for speed: two arrays
    that agree on every sampled byte collide. Vector sets that differ
    *densely* (distinct datasets, shuffled batches, re-embedded queries)
    always get distinct keys; but two arrays differing only on a span
    shorter than the sample stride (one edited row of a very large X —
    whether edited in place or freshly allocated) can collide and hit the
    other's cached artifacts. Callers doing sparse row-level updates to
    huge cached query sets should bypass the cache (``adopt`` prebuilt
    indexes, or a fresh engine) rather than rely on the fingerprint.
    """
    a = np.ascontiguousarray(np.asarray(a))
    h = hashlib.sha1()
    h.update(repr((a.shape, str(a.dtype), a.nbytes)).encode())
    flat = a.reshape(-1).view(np.uint8) if a.size else a.reshape(-1)
    if flat.nbytes <= _FP_SAMPLE_BYTES:
        h.update(flat.tobytes())
    else:
        # odd stride: coprime with the element size, so samples cycle
        # through every byte offset within f32/f64 elements (an even
        # stride would only ever see mantissa-LSB bytes and alias
        # arrays differing in exponent/high-mantissa bits)
        stride = (flat.nbytes // _FP_SAMPLE_BYTES) | 1
        h.update(np.ascontiguousarray(flat[::stride]).tobytes())
        h.update(flat[:2048].tobytes())
        h.update(flat[-2048:].tobytes())
    return h.hexdigest()[:16]


class _LRU(OrderedDict):
    def __init__(self, cap: int):
        super().__init__()
        self.cap = cap

    def touch(self, key):
        if key in self:
            self.move_to_end(key)
            return self[key]
        return None

    def put(self, key, value):
        self[key] = value
        self.move_to_end(key)
        while len(self) > self.cap:
            self.popitem(last=False)


class JoinEngine:
    """Persistent join service over one data side Y.

    Parameters
    ----------
    Y : (N, d) data vectors (the side that gets indexed / sharded).
    build_kw : kwargs forwarded to ``graph.build_index`` /
        ``build_merged_index`` (``k``, ``degree``, ``style``, ...).
    default : the ``JoinConfig`` used when a call supplies none.
    n_shards : >1 shards Y over that many devices (MI methods shard the
        merged indexes row-wise; ``nlj`` runs the mesh NLJ driver, with
        hybrid dimension+vector partitioning when the ``MeshPlan``
        decision rule picks it). ``0`` means one shard per visible JAX
        device; requesting more shards than devices raises early with a
        clear error (``distributed.MeshPlan.plan``).
    mesh, shard_axes : optionally supply an existing mesh (e.g. the
        production ``(pod, data, model)`` mesh) instead of the planned
        mesh the engine builds on demand.
    carry_window : how many completed queries the streaming path keeps
        as seed donors for future batches.
    max_cached_indexes : LRU capacity for per-X artifacts (query index,
        merged index, sharded index — each keyed by X's fingerprint).
    metrics : an ``obs.Metrics`` registry to accumulate into (the
        process-global default registry unless a private one is passed
        for isolation). Every finished join publishes its ``JoinStats``
        here, artifact-cache hits/misses are counted per kind, and
        ``metrics_snapshot()`` / ``cumulative_stats()`` read it back.
    metric : the table's space, ``"l2"`` or ``"ip"`` (see the module
        header); every index the engine builds and every join it serves
        uses it. ``"ip"`` raises for ``n_shards > 1`` and for a quant mode
        other than ``"off"`` (in ``build_kw``, ``default`` or a call's
        config).
    """

    def __init__(self, Y, *, build_kw: dict | None = None,
                 default: JoinConfig | None = None, n_shards: int = 1,
                 mesh=None, shard_axes=("data",), carry_window: int = 4096,
                 max_cached_indexes: int = 4,
                 metrics: obs_metrics.Metrics | None = None,
                 metric: str = "l2"):
        self.Y = jnp.asarray(Y)
        self.build_kw = dict(build_kw or {})
        self.default = default or JoinConfig()
        self.n_shards = (int(n_shards) if n_shards
                         else len(jax.devices()))   # 0 = one per device
        self.metric = check_metric(metric)
        if metric != "l2":
            if self.n_shards > 1:
                raise ValueError(
                    f"metric {metric!r} runs on one device: the mesh "
                    f"driver assumes L2 (n_shards={self.n_shards})")
            self._check_quant(self.build_kw.get("quant", "off"),
                              "the index build")
            self._check_quant(self.default.quant, "the default config")
        self._mesh = mesh
        self._shard_axes = shard_axes
        self._plans: dict[bool, Any] = {}    # MeshPlan per traversal kind
        self._nlj_steps: dict = {}           # sharded-NLJ compiled state
        self.carry_window = int(carry_window)
        self.metrics = metrics if metrics is not None else \
            obs_metrics.metrics()

        self._index_y: GraphIndex | None = None
        self._index_x = _LRU(max_cached_indexes)
        self._merged = _LRU(max_cached_indexes)
        self._sharded = _LRU(max_cached_indexes)
        # Compressed tier stores mirror the index artifacts they compress
        # (one per shard for the sharded path), keyed by (tier name,
        # artifact kind[, X fingerprint]). FilterCascades are assembled
        # from this one cache, so tiers are shared across modes (a
        # sketch8 join reuses the int8 store an sq8 join built).
        self._tier_stores = _LRU(4 * max_cached_indexes)
        self.build_counts: dict[str, int] = {
            "index_y": 0, "index_x": 0, "merged": 0, "sharded": 0,
            "quant": 0, "sketch": 0, "pdx": 0}
        self.build_seconds = 0.0
        self.serve_stats: dict[str, int] = {
            "joins": 0, "batches": 0, "queries": 0, "pairs": 0}

        # streaming state (global query ids, carried work-sharing cache).
        # Under a quantized mode the carry window holds int8 codes +
        # norms instead of f32 vectors (streaming-side compression): the
        # parent-assignment matmuls then run int8 as well.
        self._stream_n = 0
        self._stream_cache: dict[int, np.ndarray] = {}
        self._stream_entry_n = 0         # cached ids, not cached queries
        self._carry_vecs: np.ndarray | None = None
        self._carry_codes: np.ndarray | None = None
        self._carry_norms: np.ndarray | None = None
        self._carry_qids = np.empty(0, np.int64)

        # LSH-sampled band-occupancy estimates (plan.LshEstimator built
        # lazily over Y), sticky per (θ, quant) so repeated requests
        # reuse one capacity (stable jit cap set); the CostTable keeps
        # warmup-calibrated per-unit costs per (method, quant) for the
        # JoinPlanner and is exported via metrics_snapshot()
        from repro.plan.cost import CostTable
        self._estimator = None
        self._planner = None
        self._cap_estimates: dict[tuple, int] = {}
        self.cost_table = CostTable()

    # -- index lifecycle ----------------------------------------------------

    @property
    def n_index_builds(self) -> int:
        return sum(self.build_counts.values())

    def _check_quant(self, quant, where: str) -> None:
        """The quant modes' certified bounds are L2 bounds: under another
        metric only ``"off"`` runs."""
        if self.metric != "l2" and quant not in (None, "off"):
            raise ValueError(
                f"metric {self.metric!r} supports quant 'off' only; "
                f"{where} asks for quant {quant!r}, whose certified "
                f"bounds assume L2")

    def _cache_event(self, kind: str, hit: bool) -> None:
        self.metrics.counter(
            f"engine.cache.{kind}.{'hit' if hit else 'miss'}").inc()

    def _build_kw_for(self, key: tuple, vecs) -> dict:
        """``build_kw`` with a ``quant`` mode resolved to a prebuilt
        cascade from the engine's tier-store cache, so a cascade-driven
        index build and the joins served from that artifact share one
        int8 store instead of quantizing the same table twice."""
        bk = dict(self.build_kw, metric=self.metric)
        mode = bk.pop("quant", None)
        if mode and mode != "off":
            from repro.quant.cascade import make_cascade
            bk["quant"] = make_cascade(
                [("int8", self.tier_store(key, "int8", vecs))])
        return bk

    def index_y(self) -> GraphIndex:
        """The data-side index G_Y (built once, reused forever)."""
        self._cache_event("index_y", self._index_y is not None)
        if self._index_y is None:
            from repro.core import graph
            t0 = time.perf_counter()
            with obs_trace.span("index/build", kind="index_y"):
                self._index_y = graph.build_index(
                    self.Y, **self._build_kw_for(("index_y",), self.Y))
            self.build_seconds += time.perf_counter() - t0
            self.build_counts["index_y"] += 1
        return self._index_y

    def index_x(self, X) -> GraphIndex:
        """Query-side index G_X (MST ordering for the HWS/SWS methods)."""
        fp = _fingerprint(X)
        hit = self._index_x.touch(fp)
        self._cache_event("index_x", hit is not None)
        if hit is None:
            from repro.core import graph
            X = jnp.asarray(X)
            t0 = time.perf_counter()
            with obs_trace.span("index/build", kind="index_x"):
                hit = graph.build_index(
                    X, **self._build_kw_for(("index_x", fp), X))
            self.build_seconds += time.perf_counter() - t0
            self.build_counts["index_x"] += 1
            self._index_x.put(fp, hit)
        return hit

    def merged_index(self, X) -> GraphIndex:
        """Merged index G_{X∪Y} (greedy phase offloaded, paper §4.4)."""
        fp = _fingerprint(X)
        hit = self._merged.touch(fp)
        self._cache_event("merged", hit is not None)
        if hit is None:
            from repro.core import graph
            t0 = time.perf_counter()
            with obs_trace.span("index/build", kind="merged"):
                merged_vecs = jnp.concatenate(
                    [self.Y, jnp.asarray(X, self.Y.dtype)], axis=0)
                hit = graph.build_index(
                    merged_vecs, n_data=int(self.Y.shape[0]),
                    **self._build_kw_for(("merged", fp), merged_vecs))
            self.build_seconds += time.perf_counter() - t0
            self.build_counts["merged"] += 1
            self._merged.put(fp, hit)
        return hit

    def sharded_index(self, X):
        """Per-shard merged indexes G_{X∪Y_s} (core/distributed.py)."""
        from repro.core import distributed
        fp = _fingerprint(X)
        hit = self._sharded.touch(fp)
        self._cache_event("sharded", hit is not None)
        if hit is None:
            t0 = time.perf_counter()
            with obs_trace.span("index/build", kind="sharded"):
                hit = distributed.build_sharded_merged_index(
                    self.Y, np.asarray(X), self.n_shards, **self.build_kw)
            if self._mesh is not None:
                mesh, axes = self._mesh, self._shard_axes
            else:
                plan = self._mesh_plan(traversal=True)
                mesh, axes = plan.make_mesh(), plan.data_axis
            hit = distributed.place_sharded_index(hit, mesh, axes)
            self.build_seconds += time.perf_counter() - t0
            self.build_counts["sharded"] += 1
            self._sharded.put(fp, hit)
        return hit

    def tier_store(self, key: tuple, tier_name: str, vecs):
        """The compressed store behind one cascade tier of one index
        artifact (built once, LRU'd).

        ``key`` names the artifact (("y",), ("index_y",), ("merged", fp),
        ("sharded", fp)); ``vecs`` is the f32 table to compress — or, for
        the sharded key, the ``ShardedMergedIndex`` whose per-shard tables
        each get their own store (per-shard scale/sketch grids).
        """
        from repro.quant.cascade import build_tier_store, tier_class

        ck = (tier_name,) + key
        hit = self._tier_stores.touch(ck)
        self._cache_event("tier_store", hit is not None)
        if hit is None:
            t0 = time.perf_counter()
            if key[0] == "sharded":
                from repro.core import distributed
                hit = distributed.build_sharded_tier(
                    tier_name, vecs, n_data=int(self.Y.shape[0]))
            else:
                hit = build_tier_store(tier_name, vecs)
            self.build_seconds += time.perf_counter() - t0
            self.build_counts[tier_class(tier_name).build_counter] += 1
            self._tier_stores.put(ck, hit)
        return hit

    def cascade_for(self, key: tuple, vecs, cfg: JoinConfig,
                    stats: JoinStats):
        """The ``FilterCascade`` (or ``ShardedCascade``) of one index
        artifact under ``cfg.quant`` — the single cache behind every
        quantized path; ``stats.quant_bytes`` accumulates what is
        resident. Returns None for non-filtering modes."""
        from repro.quant.cascade import TIERS_BY_MODE, make_cascade

        self._check_quant(cfg.quant, "the join")
        if cfg.quant not in QUANT_FILTER_MODES:
            return None
        names = TIERS_BY_MODE[cfg.quant]
        stores = [(n, self.tier_store(key, n, vecs)) for n in names]
        if key[0] == "sharded":
            from repro.core.distributed import ShardedCascade
            casc = ShardedCascade(names=tuple(n for n, _ in stores),
                                  stores=tuple(s for _, s in stores))
        else:
            casc = make_cascade(stores)
        stats.quant_bytes += casc.nbytes
        return casc

    def warm_quant(self, X, cfg: JoinConfig | None = None, *,
                   method: str | None = None) -> None:
        """Pre-build the cascade tier stores a join of ``X`` would use
        (no-op unless the resolved config names a filtering quant mode).

        The single owner of the artifact-key scheme — benchmarks and
        deployments warm through this instead of mirroring the keys."""
        cfg = self._resolve(cfg, method, None)
        if cfg.quant not in QUANT_FILTER_MODES:
            return
        if cfg.method == "nlj":
            key, vecs = ("y",), self.Y
        elif self.n_shards > 1:
            key, vecs = ("sharded", _fingerprint(X)), self.sharded_index(X)
        elif cfg.method in _MI_METHODS:
            key, vecs = ("merged", _fingerprint(X)), self.merged_index(X).vecs
        else:
            key, vecs = ("index_y",), self.index_y().vecs
        self.cascade_for(key, vecs, cfg, JoinStats())

    def drop_caches(self) -> None:
        """Release every cached index artifact and tier store (the
        tenant-unload path of ``serve.JoinService``). ``Y`` itself and
        the build counters stay; the next join rebuilds on demand."""
        self._index_y = None
        self._index_x.clear()
        self._merged.clear()
        self._sharded.clear()
        self._tier_stores.clear()
        self._nlj_steps.clear()   # device-resident sharded Y + steps
        self._plans.clear()

    def adopt(self, *, index_y: GraphIndex | None = None, X=None,
              index_x: GraphIndex | None = None,
              index_merged: GraphIndex | None = None) -> None:
        """Install prebuilt artifacts (no build counted) — the compat path
        for callers that constructed indexes themselves. An index built in
        another metric than the engine's is refused."""
        for ix in (index_y, index_x, index_merged):
            if ix is not None and ix.metric != self.metric:
                raise ValueError(
                    f"an index built under metric {ix.metric!r} cannot "
                    f"serve an engine of metric {self.metric!r}")
        if index_y is not None:
            self._index_y = index_y
        if index_x is not None:
            if X is None:
                raise ValueError("adopting index_x requires X")
            self._index_x.put(_fingerprint(X), index_x)
        if index_merged is not None:
            if X is None:
                raise ValueError("adopting index_merged requires X")
            self._merged.put(_fingerprint(X), index_merged)

    # -- configuration ------------------------------------------------------

    def _resolve(self, cfg: JoinConfig | None, method: str | None,
                 theta: float | None) -> JoinConfig:
        cfg = cfg or self.default
        rep: dict[str, Any] = {}
        if method is not None:
            rep["method"] = method
        if theta is not None:
            rep["theta"] = float(theta)
        return dataclasses.replace(cfg, **rep) if rep else cfg

    def _mesh_plan(self, *, traversal: bool):
        """The engine's ``MeshPlan`` for (N_y, d, n_shards) — vector
        partitioning for graph traversal, hybrid-eligible for the exact
        NLJ path. Validates shards ≤ devices with a clear error."""
        from repro.core import distributed
        plan = self._plans.get(traversal)
        if plan is None:
            plan = distributed.MeshPlan.plan(
                int(self.Y.shape[0]), int(self.Y.shape[1]),
                self.n_shards, traversal=traversal)
            self._plans[traversal] = plan
        return plan

    # -- one-shot joins -----------------------------------------------------

    @obs_trace.call_span("join")
    def join(self, X, cfg: JoinConfig | None = None, *,
             method: str | None = None, theta: float | None = None,
             index_y: GraphIndex | None = None,
             index_x: GraphIndex | None = None,
             index_merged: GraphIndex | None = None) -> JoinResult:
        """Join X against the engine's Y. Cached indexes are reused;
        whatever the method needs and is missing is built (and counted).

        ``cfg.quant`` routes the distance hot path through the cached
        ``FilterCascade`` companion of whichever index artifact the
        method uses (filter on certified lower bounds walked through the
        tier chain, exact f32 re-rank of the ambiguous band — emitted
        pairs are unchanged)."""
        from repro.core.join import cascade_join_pairs

        cfg = self._resolve(cfg, method, theta)
        with obs_trace.span("join/prepare"):
            X = jnp.asarray(X)
        stats = JoinStats()
        if index_y is not None or index_x is not None \
                or index_merged is not None:
            self.adopt(index_y=index_y, X=X if (index_x is not None or
                                                index_merged is not None)
                       else None,
                       index_x=index_x, index_merged=index_merged)

        if cfg.method == "nlj":
            if self.n_shards > 1:
                return self._done(
                    self._join_sharded_nlj(X, cfg, stats), X, cfg)
            t0 = time.perf_counter()
            casc = self.cascade_for(("y",), self.Y, cfg, stats)
            pairs, counts = cascade_join_pairs(
                X, self.Y, cfg.theta, casc, impl=cfg.traversal.dist_impl,
                early_exit=early_exit_enabled(cfg.traversal),
                metric=self.metric)
            stats.n_rerank = counts["n_rerank"]
            if counts["escalated"]:
                stats.n_esc8 = counts["escalated"][0]
            stats.n_dims_scanned += counts["dims_scanned"]
            stats.n_dims_total += counts["dims_total"]
            stats.other_seconds = time.perf_counter() - t0
            stats.n_dist = int(X.shape[0]) * int(self.Y.shape[0])
            return self._done(JoinResult(pairs=pairs, stats=stats), X,
                              cfg)

        if self.n_shards > 1:
            return self._done(self._join_sharded(X, cfg, stats), X,
                              cfg)

        all_pairs: list[np.ndarray] = []
        t0 = time.perf_counter()
        if cfg.method in _MI_METHODS:
            with obs_trace.span("join/prepare"):
                merged = self.merged_index(X)
                casc = self.cascade_for(
                    ("merged", _fingerprint(X)), merged.vecs, cfg, stats)
            stats.other_seconds += time.perf_counter() - t0
            W.run_mi_join(X, merged, cfg, stats, all_pairs, cascade=casc)
        else:
            with obs_trace.span("join/prepare"):
                iy = self.index_y()
                ix = (self.index_x(X)
                      if cfg.method in ("es_hws", "es_sws") else None)
                casc = self.cascade_for(("index_y",), iy.vecs, cfg, stats)
            stats.other_seconds += time.perf_counter() - t0
            W.run_search_join(X, iy, ix, cfg, stats, all_pairs,
                              cascade=casc)

        pairs = (np.concatenate(all_pairs, axis=0) if all_pairs
                 else np.empty((0, 2), np.int64))
        return self._done(JoinResult(pairs=pairs, stats=stats), X, cfg)

    def sweep(self, X, thetas, cfg: JoinConfig | None = None, *,
              method: str | None = None) -> list[JoinResult]:
        """Threshold sweep: one index build amortized over all thetas."""
        return [self.join(X, cfg, method=method, theta=float(t))
                for t in thetas]

    def _join_sharded(self, X: Array, cfg: JoinConfig,
                      stats: JoinStats) -> JoinResult:
        """Mesh MI join: Y partitioned over devices, waves replicated,
        pair pools band-compacted and merged on device (one fused
        assembly transfer per wave)."""
        from repro.core import distributed
        if cfg.method not in _MI_METHODS:
            raise NotImplementedError(
                f"sharded execution supports {_MI_METHODS} and 'nlj', "
                f"not {cfg.method!r} (work-sharing caches are "
                f"per-device)")
        if self._mesh is not None:        # user-supplied mesh wins
            mesh, axes, plan = self._mesh, self._shard_axes, None
        else:
            mesh, axes = None, None
            plan = self._mesh_plan(traversal=True)
        smi = self.sharded_index(X)
        # one tier store per shard (per-shard scale and sketch grids),
        # cached alongside the sharded index they compress
        casc = self.cascade_for(("sharded", _fingerprint(X)), smi, cfg,
                                stats)
        # adapt ⇒ hybrid BBFS for every query: a sound superset of the
        # per-query adaptive split (per-shard OOD prediction would need
        # per-shard side tables; the hybrid path subsumes the BFS one).
        hybrid = cfg.method == "es_mi_adapt"
        # seed the merge StickyCap of the two-cap loop from the LSH
        # estimate's per-shard band — advisory; the driver's retry loop
        # owns correctness. The rerank cap keeps its configured cold
        # start: the gather dispatch is capacity-shaped, and the sketch
        # superset systematically overshoots the int8-tier band, so a
        # seeded re-rank width would trade the (amortized, batch-wide)
        # grow-and-retry for permanently inflated gather traffic.
        mcap0 = self.estimate_merge_cap(
            np.asarray(X, np.float32), cfg,
            limit=int(cfg.traversal.pool_cap))
        t0 = time.perf_counter()
        pairs, dstats = distributed.distributed_mi_join(
            X, smi, mesh, axes, theta=cfg.theta, cfg=cfg.traversal,
            wave_size=cfg.wave_size, hybrid=hybrid, cascade=casc,
            n_data=int(self.Y.shape[0]), overlap=W.overlap_enabled(cfg),
            plan=plan, merge_cap=mcap0)
        # dstats is a field-complete JoinStats (one per shard, reduced via
        # merge); it times its own wait/assembly phases, so only the wall
        # clock it did NOT attribute lands in expand_seconds
        stats.expand_seconds += max(
            0.0, time.perf_counter() - t0
            - dstats.wait_seconds - dstats.other_seconds)
        stats = stats.merge(dstats)
        # drop padded sentinel rows (Y padded up to shard_size * n_shards)
        pairs = pairs[pairs[:, 1] < self.Y.shape[0]]
        return JoinResult(pairs=pairs, stats=stats)

    def _join_sharded_nlj(self, X: Array, cfg: JoinConfig,
                          stats: JoinStats, offset: int = 0) -> JoinResult:
        """Mesh exact NLJ: the ``MeshPlan`` may move devices from the
        row axis to the dim axis (hybrid dimension+vector partitioning;
        psum partial-sum combine). Distances are exact f32 — pairs are
        identical to the single-device NLJ under every quant mode, which
        only ever changes *work*, never pairs. θ is a runtime argument
        of the cached compiled step, so streamed batches and threshold
        sweeps run at a flat compile count (``JoinService`` tenants can
        therefore run sharded)."""
        from repro.core import distributed
        plan = self._mesh_plan(traversal=False)
        # predicted per-(query, shard) *true* in-range occupancy seeds
        # the merged pool's StickyCap — this pool holds exact-θ pairs,
        # so the sketch-band superset (which scales with N_y) would
        # inflate the host-side merged-pool transfer for nothing
        mcap0 = self.estimate_merge_cap(
            np.asarray(X, np.float32), cfg, limit=int(self.Y.shape[0]),
            exact=True)
        t0 = time.perf_counter()
        pairs, dstats = distributed.distributed_nlj_join(
            np.asarray(X), np.asarray(self.Y), plan, theta=cfg.theta,
            wave_size=cfg.wave_size, step_cache=self._nlj_steps,
            merge_cap=mcap0)
        stats.expand_seconds += max(
            0.0, time.perf_counter() - t0
            - dstats.wait_seconds - dstats.other_seconds)
        stats = stats.merge(dstats)
        if offset:
            pairs = pairs.copy()
            pairs[:, 0] += offset
        return JoinResult(pairs=pairs, stats=stats)

    # -- streaming ----------------------------------------------------------

    @property
    def n_submitted(self) -> int:
        return self._stream_n

    def reset_stream(self) -> None:
        self._stream_n = 0
        self._stream_cache.clear()
        self._stream_entry_n = 0
        self._carry_vecs = None
        self._carry_codes = None
        self._carry_norms = None
        self._carry_qids = np.empty(0, np.int64)

    @obs_trace.call_span("join")
    def submit(self, X_batch, cfg: JoinConfig | None = None, *,
               method: str | None = None,
               theta: float | None = None) -> JoinResult:
        """Join one streaming batch; result pairs carry *global* query ids
        (``engine.n_submitted`` at call time + local position).

        Batches are padded into waves. For ``es_sws``/``es_hws`` the
        work-sharing cache persists across calls: each query seeds from
        the cache entry of the nearest previously-completed query instead
        of s_Y, so later batches keep getting cheaper (the streaming form
        of the paper's MST parent order).
        """
        from repro.core.join import cascade_join_pairs

        cfg = self._resolve(cfg, method, theta)
        if self.n_shards > 1 and cfg.method not in _MI_METHODS \
                and cfg.method != "nlj":
            raise NotImplementedError(
                "sharded streaming supports 'nlj' and the merged-index "
                "methods; the work-sharing-cache methods "
                f"{_SEARCH_METHODS} run single-device (n_shards=1)")
        with obs_trace.span("join/prepare"):
            X_batch = jnp.asarray(X_batch)
        nb = int(X_batch.shape[0])
        offset = self._stream_n
        stats = JoinStats()

        if cfg.method == "nlj" and self.n_shards > 1:
            result = self._join_sharded_nlj(X_batch, cfg, stats, offset)
        elif cfg.method in _MI_METHODS and self.n_shards > 1:
            result = self._join_sharded(X_batch, cfg, stats)
            if offset:
                result.pairs[:, 0] += offset
        elif cfg.method == "nlj":
            t0 = time.perf_counter()
            casc = self.cascade_for(("y",), self.Y, cfg, stats)
            pairs, counts = cascade_join_pairs(
                X_batch, self.Y, cfg.theta, casc,
                impl=cfg.traversal.dist_impl,
                early_exit=early_exit_enabled(cfg.traversal),
                metric=self.metric)
            stats.n_rerank = counts["n_rerank"]
            if counts["escalated"]:
                stats.n_esc8 = counts["escalated"][0]
            stats.n_dims_scanned += counts["dims_scanned"]
            stats.n_dims_total += counts["dims_total"]
            pairs[:, 0] += offset
            stats.other_seconds = time.perf_counter() - t0
            stats.n_dist = nb * int(self.Y.shape[0])
            result = JoinResult(pairs=pairs, stats=stats)
        elif cfg.method in _MI_METHODS:
            # the merged index must contain the batch's query nodes, so MI
            # streaming pays one (cached, fingerprint-keyed) build per
            # distinct batch — greedy work offloaded to construction.
            all_pairs: list[np.ndarray] = []
            with obs_trace.span("join/prepare"):
                merged = self.merged_index(X_batch)
                casc = self.cascade_for(
                    ("merged", _fingerprint(X_batch)), merged.vecs, cfg,
                    stats)
            W.run_mi_join(X_batch, merged, cfg, stats, all_pairs,
                          qid_offset=offset, cascade=casc,
                          capctl=self._seeded_capctl(X_batch, cfg,
                                                     cfg.traversal))
            pairs = (np.concatenate(all_pairs, axis=0) if all_pairs
                     else np.empty((0, 2), np.int64))
            result = JoinResult(pairs=pairs, stats=stats)
        else:
            result = self._submit_search(X_batch, cfg, stats, offset)

        self._stream_n = offset + nb
        self._batch_done(result, nb, cfg)
        return result

    @staticmethod
    def _annotate_call(stats: JoinStats) -> None:
        """The call's lane-iteration counters on its ``join`` span."""
        obs_trace.annotate_call(
            lane_iters=stats.n_lane_iters,
            hybrid_lane_iters=stats.n_hybrid_lane_iters)

    def _batch_done(self, result: JoinResult, nb: int,
                    cfg: JoinConfig | None = None) -> None:
        self._annotate_call(result.stats)
        self.serve_stats["batches"] += 1
        self.serve_stats["queries"] += nb
        self.serve_stats["pairs"] += len(result.pairs)
        result.stats.publish(self.metrics)
        self.metrics.counter("engine.batches").inc()
        self.metrics.counter("engine.queries").inc(nb)
        self.metrics.counter("engine.pairs").inc(len(result.pairs))
        self._observe_cost(cfg, nb, result.stats)

    def submit_many(self, jobs) -> list[JoinResult]:
        """Submit several streaming batches, interleaving waves across
        batch boundaries where the pipeline allows it.

        ``jobs`` is a sequence of ``(X_batch, cfg)`` pairs (``cfg`` may
        be None for the engine default, or carry per-batch θ / method /
        quant — the per-request knobs of the serving front end). Returns
        one ``JoinResult`` per job, pair-identical to calling
        ``submit()`` on each job in order.

        Consecutive search-path jobs (``index``/``es``/``es_hws``/
        ``es_sws``) that agree on (method, quant, wave_size) and have
        the wave pipeline enabled are run as one pipelined *group*: the
        final wave of batch *k* stays in flight while batch *k+1*'s
        first wave launches from its seed feedback, so the admission
        front end (``serve.JoinService``) never pays a pipeline drain
        between back-to-back batches. The seed-overlay argument is the
        same as within one batch — feedback entries equal the prefix of
        the full cache entry — so pair sets and work-sharing cache
        contents are unchanged. NLJ / merged-index jobs have no
        cross-batch seed dependency to hide and fall back to ``submit``.
        """
        resolved = [(X, self._resolve(cfg, None, None)) for X, cfg in jobs]
        results: list[JoinResult] = []
        i = 0
        while i < len(resolved):
            X, cfg = resolved[i]
            if not (cfg.method in _SEARCH_METHODS
                    and W.overlap_enabled(cfg) and self.n_shards == 1):
                results.append(self.submit(X, cfg))
                i += 1
                continue
            key = (cfg.method, cfg.quant, cfg.wave_size)
            j = i + 1
            while j < len(resolved):
                X2, c2 = resolved[j]
                if ((c2.method, c2.quant, c2.wave_size) != key
                        or not W.overlap_enabled(c2)):
                    break
                j += 1
            group = []
            for X2, c2 in resolved[i:j]:
                offset = self._stream_n
                self._stream_n += int(X2.shape[0])
                group.append((jnp.asarray(X2), c2, JoinStats(), offset))
            with obs_trace.call_span("join"):
                outs = self._submit_search_group(group)
                self._annotate_call(functools.reduce(
                    JoinStats.merge, (r.stats for r in outs)))
            for (X2, c2, _, _), res in zip(group, outs):
                self._batch_done(res, int(X2.shape[0]), c2)
            results.extend(outs)
            i = j
        return results

    def _submit_search(self, X_batch: Array, cfg: JoinConfig,
                       stats: JoinStats, offset: int) -> JoinResult:
        """Streaming search-path waves, double-buffered like
        ``waves.run_search_join``: wave *k+1* is dispatched from wave
        *k*'s seed feedback (the carry window needs only the wave's
        query codes, which exist before traversal), while the host
        assembles wave *k*'s pairs and work-sharing cache in the shadow
        of the device. ``overlap`` off serializes the same primitives."""
        return self._submit_search_group(
            [(X_batch, cfg, stats, offset)])[0]

    def _submit_search_group(self, group) -> list[JoinResult]:
        """Pipelined search-path waves over one *or several* batches.

        ``group`` is a list of ``(X_batch, cfg, stats, offset)`` jobs
        that share (method, quant, wave_size). With one job this is
        exactly the old per-batch pipeline; with several (the
        ``submit_many`` group path) the pending wave is carried *across
        the batch boundary*: batch *k+1*'s first wave launches from the
        seed-feedback overlay while batch *k*'s last wave is still being
        assembled, so back-to-back admitted batches never drain the
        pipeline. Pairs, stats attribution, and work-sharing cache
        contents are per-job and identical to sequential ``submit``
        calls (the overlay/tombstone machinery is shared engine state
        either way)."""
        iy = self.index_y()
        sy = int(iy.start)
        all_pairs: list[list[np.ndarray]] = [[] for _ in group]
        # seed overlay: feedback entries of the wave whose full cache
        # update is still pending (equal to the first S ids that
        # update_sws_cache will write for the same queries)
        overlay: dict[int, np.ndarray] = {}
        seed_cache = ChainMap(overlay, self._stream_cache)
        pending: tuple[int, W.WaveHandles] | None = None
        k = 0                            # wave index across the group

        def drain(j: int, h: W.WaveHandles) -> None:
            _, cfg_j, stats_j, _ = group[j]
            out = W.assemble_wave(h, stats_j)
            all_pairs[j].append(out.pairs)
            if cfg_j.method in _CACHING_METHODS:
                t1 = time.perf_counter()
                with obs_trace.span("wave/cache_update", wave=h.wave):
                    self._stream_entry_n = W.update_sws_cache(
                        self._stream_cache, out, h.qids, cfg_j, stats_j,
                        self._stream_entry_n)
                    for q in h.qids[h.lane_valid]:
                        overlay.pop(int(q), None)
                    # donors evicted from the carry before their cache
                    # entry landed (carry_window < wave_size): drop the
                    # entry now that update_sws_cache wrote it, as the
                    # sequential update-then-evict order would have
                    for q in h.tombstones:
                        gone = self._stream_cache.pop(int(q), None)
                        if gone is not None:
                            self._stream_entry_n -= len(gone)
                            stats_j.cache_tombstones += 1
                stats_j.other_seconds += time.perf_counter() - t1

        for j, (X_batch, cfg, stats, offset) in enumerate(group):
            casc = self.cascade_for(("index_y",), iy.vecs, cfg, stats)
            int8 = casc.tier("int8") if casc is not None else None
            S = cfg.traversal.seeds_max
            nb = int(X_batch.shape[0])
            X_np = np.asarray(X_batch, np.float32)
            caching = cfg.method in _CACHING_METHODS
            ov = W.overlap_enabled(cfg)
            capctl = W.RerankCap(W.effective_tcfg(cfg),
                                 init_cap=self.estimate_rerank_cap(
                                     X_np, cfg))

            for c0 in range(0, nb, cfg.wave_size):
                local = np.arange(c0, min(c0 + cfg.wave_size, nb))
                qids_l, lane_valid = W.pad_wave(local, cfg.wave_size)
                qids_g = qids_l + offset
                # gather the wave on the host: a device-side
                # X_batch[qids] would jit one gather per distinct batch
                # length, where serving sees arbitrary request sizes —
                # this transfer is (wave_size, d) regardless
                xw = jnp.asarray(X_np[qids_l])
                # queries are encoded on the cascade grids exactly once
                # per wave: the codes drive parent assignment, the carry
                # window, *and* the traversal (streaming compression)
                qc = casc.encode(xw) if casc is not None else None
                qc8 = (qc[casc.names.index("int8")]
                       if int8 is not None else None)

                t0 = time.perf_counter()
                parent = self._assign_parents(X_np[qids_l], qc8, int8,
                                              qids_g, lane_valid, caching)
                seeds, seeds_valid = W.seeds_from_cache(
                    qids_g, lane_valid, parent, seed_cache, sy,
                    cfg.wave_size, S, stats=stats)
                stats.other_seconds += time.perf_counter() - t0

                h = W.launch_search_wave(iy, xw, qids_g, lane_valid, cfg,
                                         stats, seeds=seeds,
                                         seeds_valid=seeds_valid,
                                         cascade=casc, qc=qc,
                                         capctl=capctl, sync=not ov,
                                         collect_seeds=caching and ov,
                                         wave=k)
                k += 1
                if ov and pending is not None:
                    drain(*pending)
                    pending = None
                if caching:
                    if ov:
                        overlay.update(W.fetch_feedback(h, stats))
                    # append this wave's donors to the carry window
                    # *before* the next wave assigns parents — codes
                    # only, no traversal dependency. Eviction may name
                    # queries whose cache entry is still pending; those
                    # become tombstones resolved at drain time.
                    t0 = time.perf_counter()
                    lv = lane_valid
                    if qc8 is not None:
                        missed = self._remember(
                            None, qids_g[lv], codes=np.asarray(qc8.q)[lv],
                            norms=np.asarray(qc8.norms)[lv], stats=stats)
                    else:
                        missed = self._remember(X_np[qids_l[lv]],
                                                qids_g[lv], stats=stats)
                    for q in missed:
                        overlay.pop(int(q), None)
                    h.tombstones.extend(missed)
                    stats.other_seconds += time.perf_counter() - t0
                if ov:
                    pending = (j, h)
                else:
                    drain(j, h)
        if pending is not None:
            drain(*pending)

        return [JoinResult(pairs=(np.concatenate(ps, axis=0) if ps
                                  else np.empty((0, 2), np.int64)),
                           stats=group[j][2])
                for j, ps in enumerate(all_pairs)]

    # -- planning (plan/: LshEstimator + CostTable + JoinPlanner) -----------

    @property
    def estimator(self):
        """The engine's ``plan.LshEstimator`` over Y (lazy; samples and
        sketches ≤2048 rows on first use, then fixed-shape forever)."""
        if self._estimator is None:
            from repro.plan import LshEstimator
            self._estimator = LshEstimator(self.Y)
        return self._estimator

    @property
    def planner(self):
        """The engine's sticky ``plan.JoinPlanner`` (estimator + cost
        table + this engine's metrics registry)."""
        if self._planner is None:
            from repro.plan import JoinPlanner
            self._planner = JoinPlanner(self.estimator, self.cost_table,
                                        metrics=self.metrics)
        return self._planner

    def estimate_rerank_cap(self, X_batch, cfg: JoinConfig) -> int | None:
        """LSH-sample estimate of the initial band-compaction capacity.

        Replaces the cold-start next-pow2 retry of ``RerankCap``: the
        ``plan.LshEstimator`` sign-sketches (SimHash) a fixed sample of
        queries against a fixed sample of Y, counts per query how many
        sampled rows the sketch tier cannot certify out of range at θ
        (the join-size/band predictor the sketches double as), and the
        capacity is the covering power of two of the scaled sample max
        (not a quantile: an overflow retry after warmup would be a
        fresh jit specialization, which the serving front end's
        flat-compile-count guarantee can't afford). Sticky per
        (θ, quant): repeated requests at the same operating point reuse
        one capacity, so the ``_finalize_wave`` cap set stays fixed
        after the first estimate (zero steady-state recompiles). The
        overflow retry remains as the safety net — emitted pairs never
        depend on the estimate.
        """
        tcfg = cfg.traversal
        if cfg.quant not in QUANT_FILTER_MODES or tcfg.rerank_cap <= 0:
            return None
        key = (round(float(cfg.theta), 6), cfg.quant, tcfg.pool_cap)
        cached = self._cap_estimates.get(key)
        if cached is not None:
            return cached
        t0 = time.perf_counter()
        est = self.estimator.estimate(X_batch, float(cfg.theta))
        cap = est.rerank_cap(tcfg.pool_cap)
        self._cap_estimates[key] = cap
        self.metrics.gauge(
            "engine.rerank_cap_estimate",
            help="LSH-sampled initial band capacity (last estimate)"
        ).set(cap)
        self.build_seconds += time.perf_counter() - t0
        return cap

    def _seeded_capctl(self, X_batch, cfg: JoinConfig,
                       tcfg) -> "W.RerankCap":
        """A ``RerankCap`` seeded from the sticky LSH estimate (falls
        back to the config cold start for non-filtering modes)."""
        return W.RerankCap(tcfg,
                           init_cap=self.estimate_rerank_cap(
                               np.asarray(X_batch, np.float32), cfg))

    def estimate_merge_cap(self, X_batch, cfg: JoinConfig, *,
                           limit: int, exact: bool = False) -> int:
        """LSH-sample seed for the sharded drivers' merged-pool
        ``StickyCap`` — the predicted worst per-(query, shard)
        occupancy, replacing the DEFAULT_MERGE_CAP cold start
        (satellite of the same estimate ``estimate_rerank_cap`` takes;
        sticky per (θ, shards, limit, exact)). ``exact`` sizes from the
        sampled true in-range counts instead of the sketch-band
        superset — the mesh NLJ merged pool only ever holds pairs past
        the exact θ check, and the superset predictor would scale its
        host transfer with N_y. Advisory-only: the drivers
        overflow-check and retry, so a low estimate costs retry time,
        never pairs."""
        key = (round(float(cfg.theta), 6), "merge", self.n_shards,
               int(limit), bool(exact))
        cached = self._cap_estimates.get(key)
        if cached is not None:
            return cached
        t0 = time.perf_counter()
        est = self.estimator.estimate(X_batch, float(cfg.theta),
                                      n_shards=self.n_shards)
        cap = est.merge_cap(int(limit), exact=exact)
        self._cap_estimates[key] = cap
        self.metrics.gauge(
            "engine.merge_cap_estimate",
            help="LSH-sampled sharded merge capacity (last estimate)"
        ).set(cap)
        self.build_seconds += time.perf_counter() - t0
        return cap

    def plan_config(self, X_batch, cfg: JoinConfig | None = None, *,
                    method: str | None = None, theta: float | None = None,
                    quant: str | None = None,
                    buckets: tuple[int, ...] | None = None) -> JoinConfig:
        """Plan one batch's operating point and return it as a concrete
        ``JoinConfig`` (the ``--plan auto`` entry point of the launch
        CLIs and benchmarks).

        Explicit ``method``/``quant`` pin those knobs; otherwise the
        ``JoinPlanner`` picks from this engine's admissible candidates
        by calibrated cost (selectivity heuristic before calibration).
        Wave size snaps to the bucket ladder; cap seeds flow through
        the sticky estimate caches (``estimate_rerank_cap`` /
        ``estimate_merge_cap``) at join time, and the hybrid-patience
        hint applies only when it changes nothing a jit cares about
        before the traversal would compile anyway. Plans are advisory:
        the planned config joins through the same overflow-checked
        drivers as a hand-tuned one and emits the identical pair set.
        """
        base = self._resolve(cfg, method, theta)
        if quant is not None:
            base = dataclasses.replace(base, quant=quant)
        if self.n_shards > 1:
            methods = ("nlj",) + _MI_METHODS
            default_method = "es_mi_adapt"
        else:
            methods = ("nlj",) + _SEARCH_METHODS + _MI_METHODS
            default_method = base.method if base.method != "nlj" else None
        if buckets is not None:
            self.planner.buckets = tuple(buckets)
        p = self.planner.plan(
            np.asarray(X_batch, np.float32), theta=float(base.theta),
            pool_cap=int(base.traversal.pool_cap),
            method=method, quant=quant, methods=methods,
            quants=((quant,) if quant is not None
                    else QUANT_MODES if self.metric == "l2" else ("off",)),
            default_method=default_method, default_quant=base.quant,
            n_shards=self.n_shards, dim=int(self.Y.shape[1]))
        rep: dict[str, Any] = {"method": p.method, "quant": p.quant,
                               "wave_size": p.wave_size}
        out = dataclasses.replace(base, **rep)
        if (p.hybrid_patience is not None
                and p.method == "es_mi_adapt"
                and p.hybrid_patience != out.traversal.hybrid_patience):
            out = dataclasses.replace(out, traversal=dataclasses.replace(
                out.traversal, hybrid_patience=p.hybrid_patience))
        return out

    def plan_request(self, n_queries: int, *, theta: float,
                     method: str | None = None,
                     quant: str | None = None) -> tuple[str, str]:
        """Cheap (estimator-free) per-request plan for the serving
        admission path: pick (method, quant) for a request that left
        them unspecified, from the cost table alone — planning a
        request never touches the device, so serve steady state stays
        at a flat compile count. Falls back to the engine's servable
        default before any calibration exists."""
        if self.n_shards > 1:
            servable = ("nlj",) + _MI_METHODS
            fallback = "nlj"
        else:
            servable = ("nlj",) + _SEARCH_METHODS
            fallback = "es_sws"
        methods = (method,) if method else servable
        quants = (quant,) if quant else (self.default.quant,)
        choice = self.planner.choose(int(n_queries), methods=methods,
                                     quants=quants)
        if choice is not None:
            return choice[0], choice[1]
        return (method or fallback), (quant or self.default.quant)

    def _assign_parents(self, xw: np.ndarray, qc8, int8_tier,
                        qids_g: np.ndarray, lane_valid: np.ndarray,
                        caching: bool) -> dict[int, int]:
        """Streaming parent = nearest completed query in the carry window.

        Under a quantized mode both sides of the nearest-donor matmul are
        int8: the wave's codes were already computed for traversal, and
        the carry window stores donor codes + norms instead of f32
        vectors (4× smaller window, d×1 bytes per donor through the
        kernel). Parent choice is a seeding heuristic, so quantized
        distances need no certification here.
        """
        if not caching or not len(self._carry_qids):
            return {}
        if qc8 is not None and self._carry_codes is not None:
            st = int8_tier.store
            # pad the donor side to the full carry window: the window
            # fills to exactly ``carry_window`` in steady state anyway,
            # and a fixed donor shape means the int8 pairwise kernel
            # compiles once per wave bucket instead of once per window
            # length while the window grows (the serving front end
            # asserts a flat compile count after warmup). Padded columns
            # are sliced off before the argmin, so parent choice is
            # unchanged.
            C, Nn = self._carry_codes, self._carry_norms
            ncar = C.shape[0]
            if ncar < self.carry_window:
                pad = self.carry_window - ncar
                C = np.concatenate(
                    [C, np.zeros((pad,) + C.shape[1:], C.dtype)])
                Nn = np.concatenate([Nn, np.zeros(pad, Nn.dtype)])
            d2 = np.asarray(ops.pairwise_sq_dists_int8(
                qc8.q, jnp.asarray(C), st.scales,
                group_size=st.group_size, xn=qc8.norms,
                yn=jnp.asarray(Nn)))[:, :ncar]
        elif self._carry_vecs is not None:
            C = self._carry_vecs
            d2 = (np.sum(xw * xw, axis=1, keepdims=True)
                  + np.sum(C * C, axis=1)[None, :] - 2.0 * xw @ C.T)
        else:
            # carry representation doesn't match the current quant mode
            # (mode switched mid-stream): fall back to rootless seeding
            return {}
        nearest = self._carry_qids[np.argmin(d2, axis=1)]
        return {int(q): int(p)
                for q, p, v in zip(qids_g, nearest, lane_valid) if v}

    def _remember(self, vecs: np.ndarray | None, qids: np.ndarray, *,
                  codes: np.ndarray | None = None,
                  norms: np.ndarray | None = None,
                  stats: JoinStats | None = None) -> list[int]:
        """Append donors to the carry window, evicting beyond capacity.

        Returns the evicted qids whose work-sharing cache entry did not
        exist yet (the pipelined path appends donors before the wave's
        cache update lands; the caller turns these into tombstones that
        drop the entry once it is written)."""
        def _append(cur, new):
            if new is None:
                return cur
            return new.copy() if cur is None else np.concatenate([cur, new])

        missed: list[int] = []

        def _evict(qs) -> None:
            for q in qs:
                gone = self._stream_cache.pop(int(q), None)
                if gone is not None:
                    self._stream_entry_n -= len(gone)
                    if stats is not None:
                        stats.cache_evictions += 1
                else:
                    missed.append(int(q))

        # a mode switch mid-stream changes the carry representation
        # (f32 vecs ↔ int8 codes); old donors can't be compared against
        # the new wave, so the window restarts rather than misalign —
        # dropped donors leave the work-sharing cache with their slots,
        # exactly like the normal eviction path below
        if (codes is not None) != (self._carry_codes is not None) \
                and len(self._carry_qids):
            _evict(self._carry_qids)
            self._carry_vecs = self._carry_codes = self._carry_norms = None
            self._carry_qids = np.empty(0, np.int64)
        self._carry_vecs = _append(self._carry_vecs, vecs)
        self._carry_codes = _append(self._carry_codes, codes)
        self._carry_norms = _append(self._carry_norms, norms)
        self._carry_qids = np.concatenate(
            [self._carry_qids, qids.astype(np.int64)])
        if len(self._carry_qids) > self.carry_window:
            keep = len(self._carry_qids) - self.carry_window
            _evict(self._carry_qids[:keep])
            for attr in ("_carry_vecs", "_carry_codes", "_carry_norms"):
                cur = getattr(self, attr)
                if cur is not None:
                    setattr(self, attr, cur[keep:])
            self._carry_qids = self._carry_qids[keep:]
        return missed

    # -- bookkeeping --------------------------------------------------------

    def _done(self, result: JoinResult, X,
              cfg: JoinConfig | None = None) -> JoinResult:
        self._annotate_call(result.stats)
        self.serve_stats["joins"] += 1
        self.serve_stats["queries"] += int(X.shape[0])
        self.serve_stats["pairs"] += len(result.pairs)
        result.stats.publish(self.metrics)
        self.metrics.counter("engine.joins").inc()
        self.metrics.counter("engine.queries").inc(int(X.shape[0]))
        self.metrics.counter("engine.pairs").inc(len(result.pairs))
        self._observe_cost(cfg, int(X.shape[0]), result.stats)
        return result

    def _observe_cost(self, cfg: JoinConfig | None, n_queries: int,
                      stats: JoinStats) -> None:
        """Offer a finished join to the planner's cost table (fastest
        per-query measurement wins, so the first post-compile batch
        sticks as the (method, quant) calibration point)."""
        if cfg is None:
            return
        if self.cost_table.observe(cfg.method, cfg.quant, n_queries,
                                   stats):
            self.metrics.counter(
                "plan.calibrations",
                help="cost-table entries (re)calibrated from finished "
                     "joins").inc()

    def metrics_snapshot(self) -> dict:
        """Plain-dict dump of the engine's metrics registry: cumulative
        ``join.*`` stats, ``engine.cache.*`` hit/miss counters, serve
        counters, and the ambient wave histograms (when the engine runs
        on the process-global registry) — plus the engine's
        warmup-calibrated planner cost table under ``"cost_table"``, so
        benchmark runs sharing a persistent engine reuse one calibration
        instead of re-measuring."""
        snap = self.metrics.snapshot()
        ct = self.cost_table.snapshot()
        if ct:
            snap["cost_table"] = ct
        return snap

    def cumulative_stats(self) -> JoinStats:
        """Engine-lifetime ``JoinStats`` aggregate, materialized back
        from the metrics registry (every join published into it)."""
        return JoinStats.from_metrics(self.metrics)
