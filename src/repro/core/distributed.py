"""Distributed vector join over an N-device mesh (ARCHITECTURE §8).

A threshold join decomposes exactly over data partitions:
``X ⋈_θ Y = ∪_s (X ⋈_θ Y_s)`` — recall composes additively and no
cross-shard traffic is needed *during* traversal. ``MeshPlan`` picks,
per (N_y, d, shards), between two partitionings of that decomposition:

  * **vector partitioning** — Y rows (and the per-shard merged indexes
    G_{X∪Y_s}) sharded over the ``data`` axis, full dims per device.
    The only layout the graph traversal can use: every hop evaluates
    whole-vector neighbor distances, so dims must be resident.
  * **hybrid dimension+vector partitioning** (HARMONY, arXiv
    2506.14707) — for the distance-dominated exact/NLJ path, a second
    ``model`` axis splits the dim axis into whole PDX slab groups;
    per-group partial squared distances are combined with a ``psum``.
    Certified early-exit algebra survives the split: a rank's local
    partial plus the reverse-triangle tail bound over *all dims it does
    not own* is a lower bound on the full distance, so any rank may
    retire a lane unilaterally (see ``hybrid_tail_bound``).

Each of the wave pipeline's transfer classes rides its own collective
(the routing table of ARCHITECTURE §8):

  * query waves — one replicating broadcast per wave;
  * pair-pool merge — on-device: each shard band-compacts its kept
    pool slots (``ops.band_compact``) and the compacted pools are
    combined with ``all_gather`` (or an S−1-step ``ppermute`` ring for
    large shard groups), so the host fetches ONE fused assembly block
    whose size tracks pair-band occupancy — not N_y, not pool width;
  * hybrid partial sums — ``psum`` over the model axis;
  * per-shard scalar stats — ride the same fused fetch.

Uneven shards: Y is padded to ``shard_size * n_shards`` with far-away
(1e3) sentinel rows. Sentinels are masked out of every per-shard scale /
center / variance statistic, pre-visited in the traversal bitmap, and
can never satisfy ``d² < θ²`` — pair sets are those of the unpadded
join. Per-shard indexes are built independently (embarrassingly
parallel offline); the merged-index offloading property is preserved
per shard because RNG pruning is local to each subgraph.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import traversal
from repro.core.types import (NO_NODE, GraphIndex, JoinStats,
                              TraversalConfig, early_exit_enabled)
from repro.kernels import ops
from repro.obs import trace as obs_trace

Array = jax.Array
# matmul-form distances contract at full f32: the certified bounds and the
# exact NLJ assume f32 rounding, which a TPU's default precision is not
_F32 = jax.lax.Precision.HIGHEST

# MeshPlan decision-rule constants (ARCHITECTURE §8). Hybrid
# dimension+vector partitioning pays off only when (a) the dim axis is
# wide enough that every model rank owns at least one whole PDX slab —
# splitting mid-slab would break the suffix-energy tail tables — and
# (b) vector partitioning alone would starve devices (too few rows per
# shard to amortize a wave).
HYBRID_ROW_FLOOR = 4096    # rows/shard below this → move devices to dims
POOL_COMBINE_RING_MIN = 8  # ppermute ring combine for groups this large
DEFAULT_MERGE_CAP = 32     # cold-start kept-pairs/lane/shard capacity


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """How many devices go to rows vs dims, and which collective merges
    the pair pool (host-side planning object, not a pytree).

    Built by :meth:`plan` from (N_y, d, shards): graph-traversal methods
    always get pure vector partitioning (``dim_shards == 1``); the
    exact/NLJ distance path is allowed to move factors of two from the
    ``data`` axis to the ``model`` axis while rows-per-shard is under
    ``HYBRID_ROW_FLOOR`` and each model rank still owns at least one
    whole PDX slab. The pool combine is ``all_gather`` for small shard
    groups and an equivalent ``ppermute`` ring for groups of
    ``POOL_COMBINE_RING_MIN``+ (same payload, no S× logical staging on
    one device's allocator).
    """
    n_shards: int                  # devices on the data (row) axis
    dim_shards: int = 1            # devices on the model (dim-slab) axis
    data_axis: str = "data"
    model_axis: str = "model"
    pool_combine: str = "all_gather"   # or "ppermute"

    def __post_init__(self):
        if self.pool_combine not in ("all_gather", "ppermute"):
            raise ValueError(
                f"unknown pool combine {self.pool_combine!r}")

    @property
    def kind(self) -> str:
        return "vector" if self.dim_shards == 1 else "hybrid"

    @property
    def n_devices(self) -> int:
        return self.n_shards * self.dim_shards

    def make_mesh(self) -> Mesh:
        """The plan's mesh, with ``Auto`` axes: the sharded joins state
        every layout in their ``shard_map`` specs and placements, so the
        per-shard tables placed on it stay plainly indexable."""
        auto = jax.sharding.AxisType.Auto
        if self.dim_shards == 1:
            return jax.make_mesh((self.n_shards,), (self.data_axis,),
                                 axis_types=(auto,))
        return jax.make_mesh((self.n_shards, self.dim_shards),
                             (self.data_axis, self.model_axis),
                             axis_types=(auto, auto))

    @classmethod
    def plan(cls, n_y: int, d: int, shards, *, devices: int | None = None,
             traversal: bool = True, pool_combine: str | None = None
             ) -> "MeshPlan":
        """Resolve ``shards`` (int, 0 or ``"auto"`` = all local devices)
        into a partitioning for a (N_y, d) data side.

        Raises a clear ``ValueError`` when more shards are requested
        than devices exist — *before* anything reaches ``shard_map``.
        """
        from repro.quant.pdx import DEFAULT_SLAB

        if devices is None:
            devices = len(jax.devices())
        if shards in (0, "auto", None):
            shards = devices
        shards = int(shards)
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if shards > devices:
            raise ValueError(
                f"{shards} shard(s) requested but only {devices} JAX "
                f"device(s) visible; use --shards auto, or force host "
                f"devices with XLA_FLAGS=--xla_force_host_platform_"
                f"device_count={shards} on CPU")
        k = 1
        if not traversal:
            while (shards % (k * 2) == 0 and shards // (k * 2) >= 1
                   and d // (k * 2) >= DEFAULT_SLAB
                   and n_y // (shards // k) < HYBRID_ROW_FLOOR):
                k *= 2
        n_shards = shards // k
        if pool_combine is None:
            pool_combine = ("ppermute"
                            if n_shards >= POOL_COMBINE_RING_MIN
                            else "all_gather")
        return cls(n_shards=n_shards, dim_shards=k,
                   pool_combine=pool_combine)


def _ring_gather(x: Array, axis: str, n: int) -> Array:
    """``all_gather`` expressed as S−1 ``ppermute`` ring shifts.

    Round ``i`` hands each rank the buffer of rank ``r − i``; a scatter
    by source rank reorders the received stack so every rank ends with
    the same (S, ...) block an ``all_gather`` would produce. Payload per
    device is identical to the ring all_gather ((S−1)·|x| received); it
    exists as the ``MeshPlan.pool_combine == "ppermute"`` routing for
    large shard groups and is asserted pair-identical to the all_gather
    path in tests/test_mesh.py.
    """
    rank = jax.lax.axis_index(axis).astype(jnp.int32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    parts = [x]
    cur = x
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        parts.append(cur)
    stack = jnp.stack(parts)        # stack[i] came from rank (r − i) % n
    src = (rank - jnp.arange(n, dtype=jnp.int32)) % n
    return jnp.zeros_like(stack).at[src].set(stack)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedMergedIndex:
    """Per-shard merged indexes G_{X∪Y_s}, stacked on a leading shard dim."""
    vecs: Array        # (S, M, d)   M = shard_size + n_query
    nbrs: Array        # (S, M, R)
    start: Array       # (S,)
    mean_nbr_dist: Array  # (S, M)
    shard_size: int = dataclasses.field(metadata=dict(static=True))
    n_query: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_shards(self) -> int:
        return self.vecs.shape[0]


def place_sharded_index(smi: ShardedMergedIndex, mesh: Mesh, axes
                        ) -> ShardedMergedIndex:
    """Lay the stacked per-shard arrays out over the mesh's shard axes,
    one shard per device, so that a wave reads each shard where it lives
    instead of streaming the whole stack from the device it was built on
    (the wave step's ``shard_map`` takes them with this same spec)."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    sh = NamedSharding(mesh, P(axes[0] if len(axes) == 1 else axes))
    return dataclasses.replace(
        smi, vecs=jax.device_put(smi.vecs, sh),
        nbrs=jax.device_put(smi.nbrs, sh),
        start=jax.device_put(smi.start, sh),
        mean_nbr_dist=jax.device_put(smi.mean_nbr_dist, sh))


def build_sharded_merged_index(Y, X, n_shards: int, **build_kw
                               ) -> ShardedMergedIndex:
    """Build one merged index per Y-shard (offline, per-shard parallel)."""
    from repro.core import graph

    Y = np.asarray(Y)
    X = np.asarray(X)
    n = Y.shape[0]
    shard_size = -(-n // n_shards)
    pad = shard_size * n_shards - n
    if pad:
        # pad with far-away sentinel rows that can never join
        Y = np.concatenate(
            [Y, np.full((pad, Y.shape[1]), 1e3, Y.dtype)], axis=0)
    vecs, nbrs, starts, mnds = [], [], [], []
    for s in range(n_shards):
        ys = Y[s * shard_size:(s + 1) * shard_size]
        gi = graph.build_merged_index(ys, X, **build_kw)
        vecs.append(np.asarray(gi.vecs))
        nbrs.append(np.asarray(gi.nbrs))
        starts.append(int(gi.start))
        mnds.append(np.asarray(gi.mean_nbr_dist))
    return ShardedMergedIndex(
        vecs=jnp.asarray(np.stack(vecs)), nbrs=jnp.asarray(np.stack(nbrs)),
        start=jnp.asarray(np.asarray(starts, np.int32)),
        mean_nbr_dist=jnp.asarray(np.stack(mnds)),
        shard_size=shard_size, n_query=X.shape[0])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedQuantStore:
    """Per-shard QuantStores, stacked on a leading shard dim.

    Each shard quantizes its own merged table on its *own* scale grid
    (local value ranges ⇒ tighter scales ⇒ smaller slack per shard).
    """
    q: Array               # (S, M, d) int8
    scales: Array          # (S, G) f32
    norms: Array           # (S, M) f32
    err: Array             # (S, M) f32
    group_size: int = dataclasses.field(metadata=dict(static=True))

    @property
    def nbytes(self) -> int:
        from repro.quant.store import arrays_nbytes
        return arrays_nbytes(self.q, self.scales, self.norms, self.err)


def quantize_sharded(smi: ShardedMergedIndex, *, n_data: int | None = None,
                     group_size: int | None = None) -> ShardedQuantStore:
    """Build one QuantStore per shard of a sharded merged index.

    ``n_data`` is the *unpadded* |Y|: when the shard split doesn't divide
    evenly, the last shard's tail rows are far-away (1e3) sentinels that
    must not contribute to the scale statistics — one poisoned group
    scale would quantize every real vector to all-zero codes and
    degenerate the filter. Sentinels are still quantized (they clip;
    their exact ``err`` keeps the bounds sound, and the exact re-rank
    rejects them like any other out-of-range candidate).
    """
    from repro.quant import store as qstore_mod

    gs = group_size or qstore_mod.DEFAULT_GROUP_SIZE
    S, M, _ = smi.vecs.shape
    pad = S * smi.shard_size - n_data if n_data is not None else 0
    stores = []
    for s in range(S):
        mask = None
        if pad and s == S - 1:
            mask = np.ones(M, bool)
            mask[smi.shard_size - pad:smi.shard_size] = False
        stores.append(qstore_mod.build_store(smi.vecs[s], group_size=gs,
                                             scale_rows=mask))
    return ShardedQuantStore(
        q=jnp.stack([s.q for s in stores]),
        scales=jnp.stack([s.scales for s in stores]),
        norms=jnp.stack([s.norms for s in stores]),
        err=jnp.stack([s.err for s in stores]),
        group_size=gs)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedSketchStore:
    """Per-shard SketchStores, stacked on a leading shard dim.

    Each shard sketches its own merged table on its *own* center μ_s;
    the rotation, isometry factor and checkpoint grid depend only on
    (d, seed) and are computed once and shared (replicated, not stacked —
    an O(d²) array per engine, not per shard).
    """
    codes: Array           # (S, M, W) uint32
    cum: Array             # (S, M, K) f32
    hs: Array              # (K,) int32 (shared checkpoint grid)
    mu: Array              # (S, d) f32
    rot: Array             # (d, d) f32 (shared)
    iso: Array             # () f32 (shared)

    @property
    def nbytes(self) -> int:
        from repro.quant.store import arrays_nbytes
        return arrays_nbytes(self.codes, self.cum, self.hs, self.mu,
                             self.rot, self.iso)


def sketch_sharded(smi: ShardedMergedIndex, *, n_data: int | None = None,
                   seed: int = 0) -> ShardedSketchStore:
    """Build one SketchStore per shard of a sharded merged index.

    Like ``quantize_sharded``, the last shard's far-away sentinel pad
    rows (when ``n_data`` doesn't divide evenly) are masked out of the
    center statistics. Sentinels are still encoded — their exact slack
    tables are huge, so their own certified bounds prune them at the
    sketch tier before any int8 work.
    """
    from repro.quant import sketch as sk

    S, M, d = smi.vecs.shape
    pad = S * smi.shard_size - n_data if n_data is not None else 0
    rotation = sk.make_rotation(d, seed)   # O(d³) once, shared per shard
    stores = []
    for s in range(S):
        mask = None
        if pad and s == S - 1:
            mask = np.ones(M, bool)
            mask[smi.shard_size - pad:smi.shard_size] = False
        stores.append(sk.build_sketch(smi.vecs[s], seed=seed,
                                      scale_rows=mask, rotation=rotation))
    return ShardedSketchStore(
        codes=jnp.stack([s.codes for s in stores]),
        cum=jnp.stack([s.cum for s in stores]),
        hs=stores[0].hs,
        mu=jnp.stack([s.mu for s in stores]),
        rot=stores[0].rot,
        iso=stores[0].iso)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedPdxStore:
    """Per-shard PdxStores, stacked on a leading shard dim.

    Each shard permutes dimensions by its *own* variance order and
    quantizes on its own per-slab grid (local statistics ⇒ earlier
    decisive slabs and tighter scales per shard); ``slab``/``dim`` are
    shared statics since every shard compresses the same-width table.
    """
    perm: Array            # (S, d) int32 per-shard dim permutations
    vp: Array              # (S, M, SL·slab) f32
    ftail: Array           # (S, M, SL) f32
    q: Array               # (S, M, SL·slab) int8
    scales: Array          # (S, SL) f32
    qslab: Array           # (S, M, SL) f32
    qtail: Array           # (S, M, SL) f32
    norms: Array           # (S, M) f32
    err: Array             # (S, M) f32
    slab: int = dataclasses.field(metadata=dict(static=True))
    dim: int = dataclasses.field(metadata=dict(static=True))

    @property
    def nbytes(self) -> int:
        from repro.quant.store import arrays_nbytes
        return arrays_nbytes(self.perm, self.vp, self.ftail, self.q,
                             self.scales, self.qslab, self.qtail,
                             self.norms, self.err)


def pdx_sharded(smi: ShardedMergedIndex, *, n_data: int | None = None,
                slab: int | None = None) -> ShardedPdxStore:
    """Build one PdxStore per shard of a sharded merged index.

    Like ``quantize_sharded``, the last shard's far-away sentinel pad
    rows (when ``n_data`` doesn't divide evenly) are masked out of both
    the variance permutation and the per-slab scale statistics; they are
    still encoded (they clip, with exact ``err``), so the certified
    bounds stay sound and the exact re-rank rejects them as usual.
    """
    from repro.quant import pdx as pdx_mod

    sl = slab or pdx_mod.DEFAULT_SLAB
    S, M, _ = smi.vecs.shape
    pad = S * smi.shard_size - n_data if n_data is not None else 0
    stores = []
    for s in range(S):
        mask = None
        if pad and s == S - 1:
            mask = np.ones(M, bool)
            mask[smi.shard_size - pad:smi.shard_size] = False
        stores.append(pdx_mod.build_pdx(smi.vecs[s], slab=sl,
                                        scale_rows=mask))
    return ShardedPdxStore(
        perm=jnp.stack([s.perm for s in stores]),
        vp=jnp.stack([s.vp for s in stores]),
        ftail=jnp.stack([s.ftail for s in stores]),
        q=jnp.stack([s.q for s in stores]),
        scales=jnp.stack([s.scales for s in stores]),
        qslab=jnp.stack([s.qslab for s in stores]),
        qtail=jnp.stack([s.qtail for s in stores]),
        norms=jnp.stack([s.norms for s in stores]),
        err=jnp.stack([s.err for s in stores]),
        slab=stores[0].slab, dim=stores[0].dim)


def build_sharded_tier(name: str, smi: ShardedMergedIndex, *,
                       n_data: int | None = None):
    """Build the per-shard stores behind one cascade tier — the sharded
    mirror of ``quant.cascade.build_tier_store`` (same names)."""
    if name == "int8":
        return quantize_sharded(smi, n_data=n_data)
    if name == "sketch1":
        return sketch_sharded(smi, n_data=n_data)
    if name == "pdx":
        return pdx_sharded(smi, n_data=n_data)
    raise ValueError(f"unknown sharded tier {name!r}")


@dataclasses.dataclass(frozen=True)
class ShardedCascade:
    """Per-shard tier stores, assembled like a ``FilterCascade`` but
    holding shard-stacked arrays (host-side container; each shard_map
    body reconstructs its *local* ``FilterCascade`` from its slices —
    see ``_local_cascade``)."""
    names: tuple           # tier names, cheap → precise
    stores: tuple          # ShardedQuantStore / ShardedSketchStore, aligned

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for s in self.stores)

    def store(self, name: str):
        return (self.stores[self.names.index(name)]
                if name in self.names else None)


def _local_cascade(names, qq, qscales, qnorms, qerr, group_size,
                   sc, scum, smu, srot, siso, shs,
                   pperm, pvp, pftail, pq, pscales, pqslab, pqtail,
                   pnorms, perr, pdx_slab, pdx_dim):
    """Reconstruct one shard's local ``FilterCascade`` from the sliced
    shard_map arguments (leading shard dim already indexed away by the
    caller's ``[0]``)."""
    from repro.quant.cascade import (FilterCascade, Int8Tier, PdxTier,
                                     SketchTier)
    from repro.quant.pdx import PdxStore
    from repro.quant.sketch import SketchStore
    from repro.quant.store import QuantStore

    tiers = []
    for name in names:
        if name == "int8":
            tiers.append(Int8Tier(QuantStore(
                q=qq, scales=qscales, norms=qnorms, err=qerr,
                group_size=group_size)))
        elif name == "sketch1":
            # codes/cum/mu are per-shard; rot/iso/hs shared (replicated)
            tiers.append(SketchTier(SketchStore(
                codes=sc, cum=scum, hs=shs, mu=smu, rot=srot, iso=siso)))
        elif name == "pdx":
            # everything per-shard (local variance order + slab grid);
            # slab/dim are shared statics
            tiers.append(PdxTier(PdxStore(
                perm=pperm, vp=pvp, ftail=pftail, q=pq, scales=pscales,
                qslab=pqslab, qtail=pqtail, norms=pnorms, err=perr,
                slab=pdx_slab, dim=pdx_dim)))
        else:
            # a new tier needs its stacked-store mirror here (and in
            # build_sharded_tier / the shard_map arg flattening) —
            # dropping it silently would change sharded results
            raise ValueError(f"no sharded reconstruction for tier {name!r}")
    return FilterCascade(tiers=tuple(tiers)) if tiers else None


def _local_mi_join(vecs, nbrs, mnd, start, qq, qscales, qnorms, qerr,
                   sc, scum, smu, srot, siso, shs,
                   pperm, pvp, pftail, pq, pscales, pqslab, pqtail,
                   pnorms, perr,
                   xw, qids, lane_valid, *,
                   theta: float, cfg: TraversalConfig, shard_size: int,
                   hybrid: bool, axis: str, group_size: int,
                   tier_names: tuple, n_shards: int, pad: int,
                   rerank_cap: int, pdx_slab: int, pdx_dim: int,
                   early_exit: bool, merge_cap: int, pool_combine: str):
    """Per-shard MI join body (runs under shard_map; all-local compute).

    With ``tier_names`` the shard reconstructs its *local*
    ``FilterCascade`` from its store slices and traverses against
    certified lower bounds (queries encoded on the local grids),
    re-ranking only the ambiguous band of its pool with exact f32
    distances before returning — the same escalation code path as the
    single-device engine, so the merged result is identical to the f32
    path. Escalation counts return per shard.

    The pair pool is merged *on device*: each shard band-compacts its
    kept pool slots into ``merge_cap`` dense columns and the compacted
    pools are combined across the shard axis (``all_gather`` or a
    ``ppermute`` ring per ``pool_combine``), so the host's assembly
    fetch is one fused (S, B, merge_cap) id block sized by pair-band
    occupancy — never the (S, B, pool_cap) raw pools. Lanes whose kept
    set outgrows ``merge_cap`` report their true occupancy in the
    ``n_keep`` output; the driver retries the wave at a grown capacity,
    so emitted pairs never depend on the cap.

    The in-shard re-rank is *sparse*: the ambiguous band is stably
    compacted into ``rerank_cap`` slots (``ops.band_compact``) and only
    those rows are gathered from the f32 table — per-shard re-rank
    traffic scales with the shard's band occupancy, not its pool
    capacity. Band entries beyond the capacity are left un-re-ranked and
    reported in the overflow output; the host driver retries the wave at
    a larger capacity, so emitted pairs never depend on the cap.
    """
    vecs, nbrs, mnd = vecs[0], nbrs[0], mnd[0]
    index = GraphIndex(vecs=vecs, nbrs=nbrs, start=start[0],
                       mean_nbr_dist=mnd, n_data=shard_size)
    rank = jax.lax.axis_index(axis).astype(jnp.int32)
    cascade = _local_cascade(tier_names, qq[0], qscales[0], qnorms[0],
                             qerr[0], group_size, sc[0], scum[0], smu[0],
                             srot, siso, shs,
                             pperm[0], pvp[0], pftail[0], pq[0],
                             pscales[0], pqslab[0], pqtail[0], pnorms[0],
                             perr[0], pdx_slab, pdx_dim)
    qc = cascade.encode(xw) if cascade is not None else None
    B = xw.shape[0]
    W = traversal.bitmap_words(vecs.shape[0])
    visited = jnp.zeros((B, W), jnp.uint32)
    node_ids = qids + shard_size
    lane = jnp.arange(B, dtype=jnp.int32)
    visited = visited.at[lane, node_ids >> 5].add(
        jnp.uint32(1) << (node_ids & 31).astype(jnp.uint32))
    if pad:
        # Pre-visit the last shard's far-away sentinel pad rows so they
        # are never probed or pooled: harmless under f32 (huge exact
        # distance) but their clipped sq8 codes carry a huge exact err,
        # collapsing the certified lower bound to 0 — they would flood
        # the pool ahead of real candidates.
        sent = jnp.arange(shard_size - pad, shard_size, dtype=jnp.int32)
        on_last = (rank == n_shards - 1).astype(jnp.uint32)
        bits = (jnp.uint32(1) << (sent & 31).astype(jnp.uint32)) * on_last
        visited = visited.at[:, sent >> 5].add(bits[None, :])
    rows = nbrs[node_ids]
    valid = jnp.broadcast_to(lane_valid[:, None], rows.shape)
    dist, ub, valid, visited, n_new, n_esc0 = traversal._probe(
        vecs, xw, rows, valid, visited, n_data=shard_size,
        traverse_nondata=hybrid, dist_impl=cfg.dist_impl,
        cascade=cascade, qc=qc, esc_th2=jnp.float32(theta) ** 2)
    best = jnp.min(dist, axis=1)
    besti = jnp.take_along_axis(jnp.where(valid, rows, NO_NODE),
                                jnp.argmin(dist, axis=1)[:, None],
                                axis=1)[:, 0]
    r = traversal.range_expand(
        index, xw, theta, cfg=cfg, n_data=shard_size, hybrid=hybrid,
        traverse_nondata=hybrid, init_idx=rows, init_dist=dist,
        init_valid=valid, visited=visited, best_dist=best, best_idx=besti,
        n_dist=n_new, cascade=cascade, qc=qc, init_ub=ub, n_esc=n_esc0,
        n_slots=jnp.int32(rows.size))
    C = r.pool_idx.shape[1]
    keep = jnp.arange(C)[None, :] < r.n_pool[:, None]
    n_rerank = jnp.zeros((B,), jnp.int32)
    n_band_over = jnp.zeros((B,), jnp.int32)
    n_dims_scanned = jnp.zeros((), jnp.int32)
    n_dims_total = jnp.zeros((), jnp.int32)
    if cascade is not None:
        # in-shard filter-then-rerank, mirroring waves._finalize_wave:
        # the confirming tier splits the pool (pool_band); certified-sure
        # entries are emitted free, and the ambiguous band is stably
        # compacted into rerank_cap slots before the exact gather — the
        # f32 rows fetched per shard scale with the band, not with C.
        th2 = jnp.float32(theta) ** 2
        sure, amb = cascade.pool_band(qc, r.pool_dist, r.pool_idx, th2)
        sure = keep & sure
        amb = keep & amb
        n_rerank = jnp.sum(amb, axis=1).astype(jnp.int32)
        cap = min(rerank_cap, C) if rerank_cap > 0 else C
        pdx = cascade.tier("pdx")
        if pdx is not None:
            # band re-rank through the PDX gather kernel: the early-exit
            # variant of the f32 slab sweep, against the shard-local
            # PdxStore mirror (same on/off pair set — see waves)
            st = pdx.store
            qcp = qc[cascade.names.index("pdx")]
            (exact, within, _, n_dims_scanned,
             n_dims_total) = ops.pdx_compact_gather_sq_dists(
                st.vp, st.ftail, st.ftail[:, 0], qcp.vp, qcp.ftail,
                qcp.ftail[:, 0], r.pool_idx, amb, cap, th2, dim=st.dim,
                early_exit=early_exit, impl=cfg.dist_impl)
            # exact is +inf where the kernel retired the lane — retired
            # certifies > θ², so the keep rule below is on/off-invariant
        else:
            exact, within, _ = ops.compact_gather_sq_dists(
                vecs, xw, r.pool_idx, amb, cap, impl=cfg.dist_impl)
        keep = sure | (within & (exact < th2))
        n_band_over = jnp.sum(amb & ~within, axis=1).astype(jnp.int32)
    # globalize kept ids and merge the pool on device: compact the kept
    # slots of this shard's pool, then combine compacted pools across
    # the shard axis so one fused assembly transfer reaches the host
    kept = keep & lane_valid[:, None] & (r.pool_idx != NO_NODE)
    gids = jnp.where(kept, r.pool_idx + rank * shard_size, NO_NODE)
    n_keep = jnp.sum(kept, axis=1).astype(jnp.int32)
    _, cand, _ = ops.band_compact(kept, gids, merge_cap)
    if pool_combine == "ppermute" and isinstance(axis, str):
        merged = _ring_gather(cand, axis, n_shards)
    else:
        merged = jax.lax.all_gather(cand, axis)
        merged = merged.reshape(n_shards, *cand.shape)
    return (merged, n_keep[None], r.overflow[None],
            r.n_dist[None], n_rerank[None], r.n_esc[None],
            n_band_over[None], n_dims_scanned[None], n_dims_total[None],
            r.n_iters[None], r.n_slots[None], r.n_lane_iters[None])


def make_distributed_mi_join(mesh: Mesh, shard_axes, smi: ShardedMergedIndex,
                             *, theta: float, cfg: TraversalConfig,
                             hybrid: bool = False,
                             cascade: ShardedCascade | None = None,
                             n_data: int | None = None,
                             rerank_cap: int | None = None,
                             merge_cap: int = DEFAULT_MERGE_CAP,
                             pool_combine: str = "all_gather"):
    """Build the pjit'd per-wave distributed join step.

    shard_axes: mesh axis name (or tuple of names) the index is sharded
    over — e.g. ``("pod", "data")`` on the production mesh. ``cascade``
    switches each shard onto its local tier chain (certified-bounds
    filter + band-compacted in-shard re-rank — the same ``FilterCascade``
    escalation as the single-device engine, reconstructed per shard);
    ``n_data`` (the unpadded |Y|) lets the body hide sentinel pad rows.
    ``rerank_cap`` overrides ``cfg.rerank_cap`` (the driver's overflow
    retry rebuilds the step at a larger capacity).

    Returns ``(step, qargs)``: ``step`` takes the tier-store arrays as
    its trailing runtime arguments (tiny placeholders when off) so
    multi-GB stores are jit *parameters*, never baked into the
    executable as constants. Call as ``step(vecs, nbrs, mnd, start,
    *qargs, xw, qids, lane_valid)``.
    """
    axes = (shard_axes,) if isinstance(shard_axes, str) else tuple(shard_axes)
    # a single shard axis passes as the bare name: P() and the
    # collectives accept it, and the body's isinstance(axis, str) gate
    # enables the ppermute ring combine. Multi-axis stacks keep the
    # tuple and can only run all_gather — reject a ppermute request
    # loudly rather than silently falling back (the driver meters
    # traffic by the requested collective).
    flat = axes[0] if len(axes) == 1 else axes
    if pool_combine == "ppermute" and not isinstance(flat, str):
        raise ValueError(
            "pool_combine='ppermute' needs a single shard axis; "
            f"got {axes!r}")
    axis_size = int(np.prod([dict(mesh.shape)[a] for a in axes]))
    # one shard per device on the shard axes — a bigger stack would be
    # silently truncated by the per-shard body (vecs[0])
    assert smi.n_shards == axis_size, (
        f"index has {smi.n_shards} shards but mesh axes {axes} provide "
        f"{axis_size} devices")
    spec_idx = P(flat)
    names = cascade.names if cascade is not None else ()
    qstore = cascade.store("int8") if cascade is not None else None
    sstore = cascade.store("sketch1") if cascade is not None else None
    pstore = cascade.store("pdx") if cascade is not None else None
    quant = qstore is not None
    sketch = sstore is not None
    pdx = pstore is not None
    assert not (sketch and not (quant or pdx)), \
        "sketch tier requires a confirming tier (int8 or pdx)"
    pad = smi.n_shards * smi.shard_size - n_data if n_data is not None else 0
    body = functools.partial(
        _local_mi_join, theta=theta, cfg=cfg, shard_size=smi.shard_size,
        hybrid=hybrid, axis=flat,
        group_size=qstore.group_size if quant else 0, tier_names=names,
        n_shards=smi.n_shards, pad=pad,
        rerank_cap=cfg.rerank_cap if rerank_cap is None else rerank_cap,
        pdx_slab=pstore.slab if pdx else 1,
        pdx_dim=pstore.dim if pdx else 0,
        early_exit=early_exit_enabled(cfg) if pdx else False,
        merge_cap=merge_cap, pool_combine=pool_combine)

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_idx, spec_idx, spec_idx, spec_idx,
                  spec_idx, spec_idx, spec_idx, spec_idx,
                  spec_idx, spec_idx, spec_idx, P(), P(), P(),
                  spec_idx, spec_idx, spec_idx, spec_idx, spec_idx,
                  spec_idx, spec_idx, spec_idx, spec_idx,
                  P(), P(), P()),
        # the merged pool is identical on every shard after the combine
        # collective → replicated out-spec: the host fetch is ONE fused
        # (S, B, merge_cap) block, not S per-shard pools
        out_specs=(P(), spec_idx, spec_idx, spec_idx, spec_idx,
                   spec_idx, spec_idx, spec_idx, spec_idx,
                   spec_idx, spec_idx, spec_idx),
        check_vma=False)

    S = smi.n_shards
    if quant:
        qargs = (qstore.q, qstore.scales, qstore.norms, qstore.err)
    else:
        # zero-size placeholders keep the shard_map arity fixed; the body
        # ignores them when quant is off
        qargs = (jnp.zeros((S, 1, 1), jnp.int8),
                 jnp.zeros((S, 1), jnp.float32),
                 jnp.zeros((S, 1), jnp.float32),
                 jnp.zeros((S, 1), jnp.float32))
    if sketch:
        # codes/cum/mu sharded; rot/iso/hs shared → replicated specs
        qargs += (sstore.codes, sstore.cum, sstore.mu, sstore.rot,
                  sstore.iso, sstore.hs)
    else:
        qargs += (jnp.zeros((S, 1, 1), jnp.uint32),
                  jnp.zeros((S, 1, 1), jnp.float32),
                  jnp.zeros((S, 1), jnp.float32),
                  jnp.zeros((1, 1), jnp.float32),
                  jnp.zeros((), jnp.float32),
                  jnp.zeros((1,), jnp.int32))
    if pdx:
        qargs += (pstore.perm, pstore.vp, pstore.ftail, pstore.q,
                  pstore.scales, pstore.qslab, pstore.qtail,
                  pstore.norms, pstore.err)
    else:
        qargs += (jnp.zeros((S, 1), jnp.int32),
                  jnp.zeros((S, 1, 1), jnp.float32),
                  jnp.zeros((S, 1, 1), jnp.float32),
                  jnp.zeros((S, 1, 1), jnp.int8),
                  jnp.zeros((S, 1), jnp.float32),
                  jnp.zeros((S, 1, 1), jnp.float32),
                  jnp.zeros((S, 1, 1), jnp.float32),
                  jnp.zeros((S, 1), jnp.float32),
                  jnp.zeros((S, 1), jnp.float32))

    @jax.jit
    def step(vecs, nbrs, mnd, start, qq, qs, qn, qe,
             sc, scum, smu, srot, siso, shs,
             pperm, pvp, pftl, pq8, psc, pqsl, pqtl, pn, pe,
             xw, qids, lane_valid):
        return mapped(vecs, nbrs, mnd, start, qq, qs, qn, qe,
                      sc, scum, smu, srot, siso, shs,
                      pperm, pvp, pftl, pq8, psc, pqsl, pqtl, pn, pe,
                      xw, qids, lane_valid)

    return step, qargs


def distributed_mi_join(X, smi: ShardedMergedIndex, mesh: Mesh | None = None,
                        shard_axes=None, *, theta: float,
                        cfg: TraversalConfig, wave_size: int = 256,
                        hybrid: bool = False,
                        cascade: ShardedCascade | None = None,
                        n_data: int | None = None, overlap: bool = True,
                        plan: MeshPlan | None = None,
                        merge_cap: int = DEFAULT_MERGE_CAP,
                        rerank_cap_init: int | None = None):
    """Host driver: waves of queries against all shards; assemble pairs.

    Pass either an explicit ``(mesh, shard_axes)`` or a ``MeshPlan``
    (which also selects the pool-combine collective). Pipelined like the
    single-device wave loop: shard waves are mutually independent, so
    wave *k+1* is dispatched before wave *k*'s merged pool is fetched —
    the host-side pair assembly runs in the shadow of the devices.
    ``overlap=False`` serializes the same steps (the bisection escape
    hatch).

    Two sticky grow-and-retry capacities (``waves.StickyCap``) keep
    results cap-independent: the in-shard re-rank band capacity and the
    merged-pool capacity (kept pairs per lane per shard). A wave that
    overflows either on any shard is retried through a step built at the
    next power-of-two capacity, sticky for the rest of the call. A retry
    re-runs the full per-shard wave, so work counters (``n_dist``,
    ``n_rerank``, …) and byte meters both accumulate over every attempt
    — they report real device work, including discarded attempts (each
    retry also bumps ``JoinStats.overflow_retries``). ``merge_cap`` and
    ``rerank_cap_init`` seed the two caps — the engine passes its LSH
    estimates (``estimate_merge_cap`` / ``estimate_rerank_cap``) so
    well-predicted runs take zero retries; the estimates stay
    advisory-only because the retry loop owns correctness.

    The assembly transfer is the all_gather/ppermute-combined
    (S, B, merge_cap) id block — host bytes per wave scale with the
    pair-band occupancy the merge capacity tracks, independent of N_y
    (per-collective traffic is metered in ``bytes_allgather`` /
    ``bytes_ppermute``; the fused fetch in ``bytes_assembly``).

    Returns ``(pairs, stats)`` where ``stats`` is a field-complete
    ``JoinStats``: one per-shard ``JoinStats`` is accumulated over the
    run (``band_occ_per_shard`` holding that shard's band total) and the
    shard group is reduced with the associative ``JoinStats.merge``.
    Host-phase time is self-attributed (``wait_seconds`` for the
    blocking per-wave transfer, ``other_seconds`` for pair assembly).
    """
    from repro.engine import waves as W

    if plan is not None:
        if mesh is None:
            mesh = plan.make_mesh()
        if shard_axes is None:
            shard_axes = plan.data_axis
    if mesh is None or shard_axes is None:
        raise ValueError("pass mesh+shard_axes or a MeshPlan")
    pool_combine = plan.pool_combine if plan is not None else "all_gather"
    X = jnp.asarray(X)
    nq = X.shape[0]
    d = int(X.shape[1])
    C = cfg.pool_cap
    S = smi.n_shards
    rcap = W.RerankCap(cfg, init_cap=rerank_cap_init)
    mcap = W.StickyCap(merge_cap, C)
    steps: dict[tuple, tuple] = {}

    def get_step():
        key = (rcap.cap if cascade is not None else C, mcap.cap)
        if key not in steps:
            steps[key] = make_distributed_mi_join(
                mesh, shard_axes, smi, theta=theta, cfg=cfg, hybrid=hybrid,
                cascade=cascade, n_data=n_data, rerank_cap=key[0],
                merge_cap=key[1], pool_combine=pool_combine)
        return steps[key]

    pairs_out = []
    shard_stats = [JoinStats() for _ in range(S)]
    band = np.zeros(S, np.int64)

    def dispatch(padded, lane_valid, wave):
        step, qargs = get_step()
        with obs_trace.span("wave/launch", wave=wave), jax.set_mesh(mesh):
            outs = step(
                smi.vecs, smi.nbrs, smi.mean_nbr_dist, smi.start, *qargs,
                X[jnp.asarray(padded)], jnp.asarray(padded),
                jnp.asarray(lane_valid))
        B = int(lane_valid.shape[0])
        combine_bytes = (S - 1) * B * mcap.cap * 4   # peer payload/device
        for st in shard_stats:
            if cascade is not None:
                st.n_rerank_gather += B * rcap.cap
                st.bytes_band += B * rcap.cap * d * 4
            if pool_combine == "ppermute":
                st.bytes_ppermute += combine_bytes
            else:
                st.bytes_allgather += combine_bytes
        return outs

    def fetch(outs, wave):
        """The blocking per-wave transfer: one fused merged-pool block
        plus the per-shard scalar stats."""
        t0 = time.perf_counter()
        with obs_trace.span("wave/fetch", wave=wave):
            outs = jax.device_get(outs)
        shard_stats[0].wait_seconds += time.perf_counter() - t0
        shard_stats[0].bytes_assembly += sum(a.nbytes for a in outs)
        return outs

    def assemble(pending) -> None:
        padded, lane_valid, outs, wave = pending

        def tally(n_dist, overflow, n_rerank, n_esc, n_dims_s, n_dims_t,
                  n_iters, n_slots, n_lane_iters):
            # per-attempt accounting: an overflow retry re-runs the FULL
            # per-shard wave (traversal included), so the work counters
            # accumulate on every fetch — the same style as dispatch()'s
            # per-attempt collective/re-rank byte meters
            per = {  # (S,) per-shard attempt totals
                "n_dist": n_dist[:, lane_valid].sum(axis=1),
                "n_overflow": overflow[:, lane_valid].sum(axis=1),
                "n_rerank": n_rerank[:, lane_valid].sum(axis=1),
                "n_esc8": n_esc[:, lane_valid].sum(axis=1),
                "n_dims_scanned": np.asarray(n_dims_s).reshape(-1),
                "n_dims_total": np.asarray(n_dims_t).reshape(-1),
                "n_iters": np.asarray(n_iters).reshape(-1),
                "n_slots": np.asarray(n_slots).reshape(-1),
                "n_lane_iters": n_lane_iters[:, lane_valid].sum(axis=1),
            }
            if hybrid:
                per["n_hybrid_lane_iters"] = per["n_lane_iters"]
            for s, st in enumerate(shard_stats):
                for k, v in per.items():
                    setattr(st, k, getattr(st, k) + int(v[s]))
            band[:] += n_rerank[:, lane_valid].sum(axis=1).astype(np.int64)

        with obs_trace.span("wave/assemble", wave=wave) as sp:
            (merged, n_keep, overflow, n_dist, n_rerank, n_esc,
             n_band_over, *counts) = fetch(outs, wave)
            tally(n_dist, overflow, n_rerank, n_esc, *counts)
            # grow-and-retry: the band capacity (in-shard re-rank) and
            # the merge capacity (kept pairs per lane per shard) are both
            # exact after one measurement, but growing the band can admit
            # more kept pairs — loop until neither overflows (bounded:
            # caps are monotone powers of two clamped to pool_cap)
            while True:
                need_band = (int(n_rerank[:, lane_valid].max())
                             if n_band_over[:, lane_valid].sum() > 0 else 0)
                # the merge check runs against the *dispatch-time*
                # capacity — the fetched block's actual width. With
                # overlap on, an earlier wave's assembly may have grown
                # the sticky mcap after this wave was dispatched;
                # occupancies in (dispatch cap, mcap.cap] would pass a
                # check against mcap.cap while this block is truncated
                # at the old width, silently dropping pairs.
                need_merge = (int(n_keep[:, lane_valid].max())
                              if (n_keep[:, lane_valid]
                                  > merged.shape[2]).any()
                              else 0)
                if not need_band and not need_merge:
                    break
                shard_stats[0].overflow_retries += 1
                if need_band:
                    rcap.grow(need_band)
                if need_merge:
                    mcap.grow(need_merge)
                (merged, n_keep, overflow, n_dist, n_rerank, n_esc,
                 n_band_over, *counts) = fetch(
                    dispatch(padded, lane_valid, wave), wave)
                tally(n_dist, overflow, n_rerank, n_esc, *counts)
            t1 = time.perf_counter()
            # (S, B, K) merged id block: every non-sentinel entry is a
            # kept (shard-global) pair for its lane
            sh, ln, sl = np.nonzero(merged != NO_NODE)
            pairs_out.append(np.stack([padded[ln], merged[sh, ln, sl]],
                                      axis=1))
            if obs_trace.recording():
                sp.set_metadata(pairs=int(ln.size))
            shard_stats[0].other_seconds += time.perf_counter() - t1

    pending = None
    for k, q0 in enumerate(range(0, nq, wave_size)):
        ids = np.arange(q0, min(q0 + wave_size, nq))
        padded, lane_valid = W.pad_wave(ids.astype(np.int32), wave_size)
        outs = dispatch(padded, lane_valid, k)
        if overlap:
            if pending is not None:
                assemble(pending)
            pending = (padded, lane_valid, outs, k)
        else:
            assemble((padded, lane_valid, outs, k))
    if pending is not None:
        assemble(pending)
    pairs = (np.concatenate(pairs_out, axis=0) if pairs_out
             else np.empty((0, 2), np.int64)).astype(np.int64)
    for s, st in enumerate(shard_stats):
        st.band_occ_per_shard = (int(band[s]),)
    stats = functools.reduce(JoinStats.merge, shard_stats)
    return pairs, stats


# ---------------------------------------------------------------------------
# hybrid dimension+vector partitioning — exact NLJ over a 2-D mesh
# ---------------------------------------------------------------------------

def _pad_cols(A: np.ndarray, k: int, slab: int) -> tuple[np.ndarray, int]:
    """Zero-pad columns so ``k`` model ranks each own the same number of
    *whole* slabs (``w`` columns each). Zero columns contribute exactly
    0.0 to every squared distance, so padded results are bit-identical
    to unpadded ones."""
    d = A.shape[1]
    n_slabs = -(-d // slab)
    per = -(-n_slabs // k)           # whole slabs per model rank
    w = per * slab
    if w * k == d:
        return np.ascontiguousarray(A, np.float32), w
    out = np.zeros((A.shape[0], w * k), np.float32)
    out[:, :d] = A
    return out, w


def slab_partial_sq_dists(X, Y, k: int, *, slab: int | None = None):
    """Unsharded reference of the hybrid partition's partial sums.

    Returns the (k, B, N) per-group partial squared distances, computed
    with the *same arithmetic* each model rank runs locally (norms +
    GEMM over the group's column slice). ``sum(axis=0)`` of this stack
    is the grouped-order total the ``psum`` combine must reproduce
    bitwise on CPU — the admissibility contract of the hybrid plan
    (tests/test_mesh.py)."""
    from repro.quant.pdx import DEFAULT_SLAB

    sl = slab or DEFAULT_SLAB
    Xp, w = _pad_cols(np.asarray(X), k, sl)
    Yp, _ = _pad_cols(np.asarray(Y), k, sl)
    parts = []
    for g in range(k):
        x = jnp.asarray(Xp[:, g * w:(g + 1) * w])
        y = jnp.asarray(Yp[:, g * w:(g + 1) * w])
        xn = jnp.sum(x * x, axis=-1, keepdims=True)
        yn = jnp.sum(y * y, axis=-1, keepdims=True)
        parts.append(xn + yn.T - 2.0 * jnp.matmul(x, y.T, precision=_F32))
    return jnp.stack(parts)


def make_hybrid_sq_dists(mesh: Mesh, plan: MeshPlan):
    """jit'd ``(Xp, Yp) → (B, N)`` exact squared distances with the dim
    axis split into whole-slab groups over the model axis and per-group
    partials combined with ``psum`` (rows replicated — the minimal
    admissibility harness for the hybrid partitioning; the production
    path is ``distributed_nlj_join``)."""
    def body(x, y):
        xn = jnp.sum(x * x, axis=-1, keepdims=True)
        yn = jnp.sum(y * y, axis=-1, keepdims=True)
        part = xn + yn.T - 2.0 * jnp.matmul(x, y.T, precision=_F32)
        if plan.dim_shards > 1:
            part = jax.lax.psum(part, plan.model_axis)
        return part

    spec = (P(None, plan.model_axis) if plan.dim_shards > 1
            else P(None, None))
    mapped = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                           out_specs=P(), check_vma=False)
    return jax.jit(mapped)


def hybrid_tail_bound(part, own_x, own_y, norm_x, norm_y, d: int):
    """Certified lower bound on the *full* squared distance available to
    a model rank that owns only one dim-slab group.

    ``part`` is the rank's exact local partial, ``own_*`` the group
    energies (local squared norms) and ``norm_*`` the full squared
    norms. By the reverse triangle inequality over every dim the rank
    does NOT own::

        part + (√(‖x‖²−own_x) − √(‖y‖²−own_y))² ≤ ‖x − y‖²

    deflated by the PDX rounding guard (``pdx.deflate_tail``) so f32
    round-off can't inflate it past the true distance. A rank may
    therefore unilaterally retire a lane when the bound exceeds θ² —
    certified early exit survives the hybrid split, and the psum'd
    retirement flag keeps every rank's keep-decision identical."""
    from repro.quant import pdx as pdx_mod

    ox = jnp.maximum(norm_x - own_x, 0.0)
    oy = jnp.maximum(norm_y - own_y, 0.0)
    rt = (jnp.sqrt(ox) - jnp.sqrt(oy)) ** 2
    return part + pdx_mod.deflate_tail(rt, norm_x + norm_y, d)


def _make_nlj_step(mesh: Mesh, plan: MeshPlan, *, rows: int, d: int,
                   merge_cap: int):
    """Compiled per-wave step of the sharded exact NLJ: rows over the
    data axis, whole-slab dim groups over the model axis (hybrid plans),
    ``psum`` partial-sum combine, certified per-rank retirement, and the
    same on-device band-compact + all_gather/ppermute pool merge as the
    MI driver. θ² is a *runtime* argument — threshold sweeps and served
    tenants reuse one executable."""
    S, k = plan.n_shards, plan.dim_shards
    daxis, maxis = plan.data_axis, plan.model_axis

    def body(x, y, th2, lane_valid):
        # x: (B, w) local dim slice;  y: (rows, w) local rows × dims
        xn = jnp.sum(x * x, axis=-1, keepdims=True)
        yn = jnp.sum(y * y, axis=-1, keepdims=True)
        part = xn + yn.T - 2.0 * jnp.matmul(x, y.T, precision=_F32)
        if k > 1:
            # full norms, certified per-rank retirement, exact combine
            nx = jax.lax.psum(xn, maxis)
            ny = jax.lax.psum(yn, maxis)
            bound = hybrid_tail_bound(part, xn, yn.T, nx, ny.T, d)
            retired = jax.lax.psum(
                (bound > th2).astype(jnp.int32), maxis)
            d2 = jax.lax.psum(part, maxis)
            kept = (retired == 0) & (d2 < th2)
        else:
            kept = part < th2
        kept = kept & lane_valid[:, None]
        rank = jax.lax.axis_index(daxis).astype(jnp.int32)
        ids = jnp.arange(rows, dtype=jnp.int32)[None, :] + rank * rows
        gids = jnp.where(kept, jnp.broadcast_to(ids, kept.shape), NO_NODE)
        n_keep = jnp.sum(kept, axis=1).astype(jnp.int32)
        _, cand, _ = ops.band_compact(kept, gids, merge_cap)
        if plan.pool_combine == "ppermute":
            merged = _ring_gather(cand, daxis, S)
        else:
            merged = jax.lax.all_gather(cand, daxis)
        return merged, n_keep[None]

    if k > 1:
        in_specs = (P(None, maxis), P(daxis, maxis), P(), P())
        out_specs = (P(), P(daxis))
    else:
        in_specs = (P(None, None), P(daxis, None), P(), P())
        out_specs = (P(), P(daxis))
    mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(mapped)


def distributed_nlj_join(X, Y, plan: MeshPlan, *, theta: float,
                         wave_size: int = 256,
                         merge_cap: int = DEFAULT_MERGE_CAP,
                         step_cache: dict | None = None):
    """Sharded exact NLJ driver — the pair-producing engine path behind
    ``MeshPlan`` hybrid plans.

    Y rows are padded to ``n_shards`` even shards with far-away (1e3)
    sentinels and sharded over the data axis; for hybrid plans the dim
    axis is zero-padded to whole slabs and split over the model axis
    (``psum`` partial-sum combine + certified per-rank retirement —
    pairs identical to the single-device exact NLJ). The kept pool is
    merged on device and fetched as one fused block per wave.

    ``step_cache`` (engine-owned dict) pins the compiled step, the
    device-resident sharded Y block, and the sticky merge capacity
    across calls: streaming submits and threshold sweeps stay at a flat
    compile count because θ² is a runtime argument.

    Returns ``(pairs, stats)``.
    """
    from repro.engine import waves as W
    from repro.quant.pdx import DEFAULT_SLAB

    cache = step_cache if step_cache is not None else {}
    X = np.asarray(X, np.float32)
    Y = np.asarray(Y, np.float32)
    n_data, d = Y.shape
    S, k = plan.n_shards, plan.dim_shards
    key = (plan, n_data, d)
    if cache.get("key") != key:
        rows = -(-n_data // S)
        Yp = Y
        if rows * S != n_data:
            Yp = np.concatenate(
                [Y, np.full((rows * S - n_data, d), 1e3, np.float32)],
                axis=0)
        Yp, w = _pad_cols(Yp, k, DEFAULT_SLAB)
        cache.clear()
        mesh = plan.make_mesh()
        # Y lives sharded, rows over the data axis (and dim groups over
        # the model axis), exactly as the step's shard_map takes it
        y_spec = P(plan.data_axis, plan.model_axis if k > 1 else None)
        cache.update(key=key, mesh=mesh, rows=rows, w=w,
                     Yp=jax.device_put(Yp, NamedSharding(mesh, y_spec)),
                     mcap=W.StickyCap(merge_cap, rows * S), steps={})
    mesh, rows, w = cache["mesh"], cache["rows"], cache["w"]
    mcap: W.StickyCap = cache["mcap"]

    def get_step():
        if mcap.cap not in cache["steps"]:
            cache["steps"][mcap.cap] = _make_nlj_step(
                mesh, plan, rows=rows, d=d, merge_cap=mcap.cap)
        return cache["steps"][mcap.cap]

    Xp, _ = _pad_cols(X, k, DEFAULT_SLAB)
    th2 = jnp.float32(theta) ** 2
    stats = JoinStats()
    pairs_out = []

    def dispatch(xw, lane_valid):
        step = get_step()
        with jax.set_mesh(mesh):
            outs = step(jnp.asarray(xw), cache["Yp"], th2,
                        jnp.asarray(lane_valid))
        B = int(lane_valid.shape[0])
        # collective meters (ARCHITECTURE §8 routing table): the pool
        # combine over the data axis and, for hybrid plans, the psum'd
        # partials / norms / retirement flags over the model axis
        combine = (S - 1) * B * mcap.cap * 4
        if plan.pool_combine == "ppermute":
            stats.bytes_ppermute += S * combine
        else:
            stats.bytes_allgather += S * combine
        if k > 1:
            stats.bytes_psum += (plan.n_devices * (k - 1)
                                 * (2 * B * rows + B + rows) * 4)
        return outs

    nq = X.shape[0]
    for q0 in range(0, nq, wave_size):
        ids = np.arange(q0, min(q0 + wave_size, nq))
        padded, lane_valid = W.pad_wave(ids.astype(np.int32), wave_size)
        xw = Xp[padded]
        outs = dispatch(xw, lane_valid)
        while True:
            t0 = time.perf_counter()
            merged, n_keep = jax.device_get(outs)
            stats.wait_seconds += time.perf_counter() - t0
            stats.bytes_assembly += merged.nbytes + n_keep.nbytes
            # check against the fetched block's width (== the dispatch
            # cap; this loop is sequential, but the invariant matches
            # the MI driver's overlap-safe check)
            if not (n_keep[:, lane_valid] > merged.shape[2]).any():
                break
            need = int(n_keep[:, lane_valid].max())
            stats.overflow_retries += 1
            mcap.grow(need)
            outs = dispatch(xw, lane_valid)
        t1 = time.perf_counter()
        sh, ln, sl = np.nonzero(merged != NO_NODE)
        pairs_out.append(np.stack([padded[ln], merged[sh, ln, sl]],
                                  axis=1))
        # logical distance count: sentinel pad rows are not real
        # comparisons, so the meter matches the single-device NLJ
        stats.n_dist += int(lane_valid.sum()) * n_data
        stats.other_seconds += time.perf_counter() - t1
    pairs = (np.concatenate(pairs_out, axis=0) if pairs_out
             else np.empty((0, 2), np.int64)).astype(np.int64)
    pairs = pairs[pairs[:, 1] < n_data]      # sentinel belt-and-braces
    stats.band_occ_per_shard = (0,) * S      # NLJ has no re-rank band
    return pairs, stats


# ---------------------------------------------------------------------------
# exact NLJ counts with 2-D (data × model) sharding — the roofline demo
# ---------------------------------------------------------------------------

def make_distributed_nlj_count(mesh: Mesh, data_axes, model_axis: str,
                               *, theta: float):
    """Exact per-query counts with Y rows sharded over data axes and the
    vector dimension sharded over the model axis (psum of partial dists)."""
    data_axes = ((data_axes,) if isinstance(data_axes, str)
                 else tuple(data_axes))

    def body(x, y):  # x: (B, d/m), y: (N/s, d/m)
        # partial squared-distance terms over the local dim slice
        xn = jnp.sum(x * x, axis=-1, keepdims=True)
        yn = jnp.sum(y * y, axis=-1, keepdims=True).T
        xy = jnp.matmul(x, y.T, precision=_F32)
        part = xn + yn - 2.0 * xy                      # (B, N/s)
        d2 = jax.lax.psum(part, model_axis)            # full squared dists
        cnt = jnp.sum(d2 < jnp.float32(theta) ** 2, axis=1).astype(jnp.int32)
        return jax.lax.psum(cnt, data_axes)            # (B,) global counts

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, model_axis), P(data_axes, model_axis)),
        out_specs=P(),
        check_vma=False)
    return jax.jit(mapped)
