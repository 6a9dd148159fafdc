"""jit'd public wrappers around the Pallas kernels.

Implementation selection:
  * ``pallas``            — compiled Pallas (TPU target).
  * ``pallas_interpret``  — Pallas interpret mode (CPU validation of the
                            exact kernel bodies; used by tests).
  * ``ref``               — pure-jnp oracle (fast on CPU; default off-TPU).
  * ``xla``               — ``topk_merge`` only: ``lax.top_k``, its
                            one path on every backend.

Wrappers pad inputs to block-divisible shapes and slice results back, with
padding arranged so it can never contaminate results (padded data rows get
+inf norms / +inf distances). Padding covers *every* caller shape —
including dimensions smaller than one block and empty inputs — for both
the f32 and the int8 kernels: blocks are chosen per-dimension via
``_grid_dim`` so the padded extent is always an exact multiple of the
block actually used.

The ``*_int8`` ops take QuantStore codes (per-dimension-group scaled int8,
``repro.quant.store``) and return the *quantized-domain* squared distance
``‖x̂ − ŷ‖²``. ``quant_lower_bound`` / ``quant_upper_bound`` convert it
into certified bounds on the true distance from the exact per-vector
quantization errors (triangle inequality):

    ‖x − y‖ ∈ [ ‖x̂ − ŷ‖ − s,  ‖x̂ − ŷ‖ + s ],   s = ‖x−x̂‖ + ‖y−ŷ‖

so a threshold test on the lower bound never rejects a true pair — the
contract the filter-then-rerank join pipeline rests on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bits as _bits
from repro.kernels import distance as _distance
from repro.kernels import gather_distance as _gd
from repro.kernels import int8 as _int8
from repro.kernels import nlj as _nlj
from repro.kernels import pdx as _pdx
from repro.kernels import ref as _ref

Array = jax.Array

_BIG = jnp.float32(1e30)


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# entry name → every implementation it resolved to, recorded when the
# entry is traced. ``chip_smoke.py`` reads it to prove that no reference
# or interpret-mode path ran on the chip.
IMPLS_USED: dict[str, set[str]] = {}


def _resolve(entry: str, impl: str | None) -> str:
    impl = impl or default_impl()
    IMPLS_USED.setdefault(entry, set()).add(impl)
    return impl


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _grid_dim(n: int, default: int, align: int) -> tuple[int, int]:
    """(padded_n, block) for one grid dimension, any n ≥ 1.

    The block is the kernel default, shrunk (align-rounded) for small n,
    so ``block | padded_n`` always holds and the kernel's divisibility
    asserts can never fire on a wrapper-padded shape.
    """
    b = min(default, _round_up(n, align))
    return _round_up(n, b), b


def _pad_rows(a: Array, n: int, fill: float = 0.0) -> Array:
    if a.shape[0] == n:
        return a
    pad = jnp.full((n - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return jnp.concatenate([a, pad], axis=0)


def _pad_axis(a: Array, n: int, axis: int, fill: float = 0.0) -> Array:
    if a.shape[axis] == n:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, n - a.shape[axis])
    return jnp.pad(a, widths, constant_values=fill)


# ---------------------------------------------------------------------------
# f32 kernels
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("impl",))
def pairwise_sq_dists(x: Array, y: Array, *, impl: str | None = None) -> Array:
    """(B, d) × (N, d) → (B, N) f32 squared L2 distances."""
    impl = _resolve("pairwise_sq_dists", impl)
    B, d = x.shape
    N, _ = y.shape
    if B == 0 or N == 0 or d == 0:
        return jnp.zeros((B, N), jnp.float32)
    if impl == "ref":
        return _ref.pairwise_sq_dists(x, y)
    Bp, bm = _grid_dim(B, 256, 8)
    Np, bn = _grid_dim(N, 512, 128)
    dp, bk = _grid_dim(d, 512, 128)
    xp = _pad_axis(_pad_rows(x, Bp), dp, axis=1)
    yp = _pad_axis(_pad_rows(y, Np), dp, axis=1)
    out = _distance.pairwise_sq_dists_pallas(
        xp, yp, bm=bm, bn=bn, bk=bk, interpret=(impl == "pallas_interpret"))
    return out[:B, :N]


@functools.partial(jax.jit, static_argnames=("impl",))
def rowwise_sq_dists(x: Array, cands: Array, *, impl: str | None = None) -> Array:
    """(B, d) × (B, K, d) → (B, K) f32 per-query candidate distances."""
    impl = _resolve("rowwise_sq_dists", impl)
    B, d = x.shape
    _, K, _ = cands.shape
    if B == 0 or K == 0 or d == 0:
        return jnp.zeros((B, K), jnp.float32)
    if impl == "ref":
        return _ref.rowwise_sq_dists(x, cands)
    Bp, bm = _grid_dim(B, 8, 8)
    Kp, bkk = _grid_dim(K, 128, 128)
    dp, dk = _grid_dim(d, 512, 128)
    xp = _pad_axis(_pad_rows(x, Bp), dp, axis=1)
    cp = _pad_axis(_pad_axis(_pad_rows(cands, Bp), Kp, axis=1), dp, axis=2)
    out = _distance.rowwise_sq_dists_pallas(
        xp, cp, bm=bm, bkk=bkk, dk=dk, interpret=(impl == "pallas_interpret"))
    return out[:B, :K]


@functools.partial(jax.jit, static_argnames=("impl",))
def pairwise_neg_ip(x: Array, y: Array, *, impl: str | None = None) -> Array:
    """(B, d) × (N, d) → (B, N) f32 −⟨x, y⟩ (the ``ip`` metric's
    dissimilarity). Zero padding adds nothing to a dot; padded rows and
    columns are sliced off."""
    impl = _resolve("pairwise_neg_ip", impl)
    B, d = x.shape
    N, _ = y.shape
    if B == 0 or N == 0 or d == 0:
        return jnp.zeros((B, N), jnp.float32)
    if impl == "ref":
        return _ref.pairwise_neg_ip(x, y)
    Bp, bm = _grid_dim(B, 256, 8)
    Np, bn = _grid_dim(N, 512, 128)
    dp, bk = _grid_dim(d, 512, 128)
    xp = _pad_axis(_pad_rows(x, Bp), dp, axis=1)
    yp = _pad_axis(_pad_rows(y, Np), dp, axis=1)
    out = _distance.pairwise_neg_ip_pallas(
        xp, yp, bm=bm, bn=bn, bk=bk, interpret=(impl == "pallas_interpret"))
    return out[:B, :N]


@functools.partial(jax.jit, static_argnames=("impl",))
def rowwise_neg_ip(x: Array, cands: Array, *, impl: str | None = None) -> Array:
    """(B, d) × (B, K, d) → (B, K) f32 per-query candidate −⟨x, c⟩. At
    d = 200 the candidates pad to 256 (the 128-lane block), as gist's 960
    pads to 1024 for ``rowwise_sq_dists``."""
    impl = _resolve("rowwise_neg_ip", impl)
    B, d = x.shape
    _, K, _ = cands.shape
    if B == 0 or K == 0 or d == 0:
        return jnp.zeros((B, K), jnp.float32)
    if impl == "ref":
        return _ref.rowwise_neg_ip(x, cands)
    Bp, bm = _grid_dim(B, 8, 8)
    Kp, bkk = _grid_dim(K, 128, 128)
    dp, dk = _grid_dim(d, 512, 128)
    xp = _pad_axis(_pad_rows(x, Bp), dp, axis=1)
    cp = _pad_axis(_pad_axis(_pad_rows(cands, Bp), Kp, axis=1), dp, axis=2)
    out = _distance.rowwise_neg_ip_pallas(
        xp, cp, bm=bm, bkk=bkk, dk=dk, interpret=(impl == "pallas_interpret"))
    return out[:B, :K]


def pairwise_dists(x: Array, y: Array, *, metric: str = "l2",
                   impl: str | None = None) -> Array:
    """The metric's pairwise distance as the program compares it with
    ``core.types.theta_key``: squared L2, or −⟨x, y⟩."""
    if metric == "ip":
        return pairwise_neg_ip(x, y, impl=impl)
    return pairwise_sq_dists(x, y, impl=impl)


def rowwise_dists(x: Array, cands: Array, *, metric: str = "l2",
                  impl: str | None = None) -> Array:
    """The metric's per-query candidate distance (``pairwise_dists``'s
    rowwise form)."""
    if metric == "ip":
        return rowwise_neg_ip(x, cands, impl=impl)
    return rowwise_sq_dists(x, cands, impl=impl)


@functools.partial(jax.jit, static_argnames=("theta", "impl"))
def nlj_count(x: Array, y: Array, *, theta: float,
              impl: str | None = None) -> Array:
    """Exact per-query join counts |{j : dist(x_b, y_j) < theta}| → (B,) i32."""
    impl = _resolve("nlj_count", impl)
    B, d = x.shape
    N, _ = y.shape
    if B == 0:
        return jnp.zeros((0,), jnp.int32)
    if N == 0 or d == 0:
        # d == 0: every distance is 0 < theta (for positive theta)
        n = N if (d == 0 and theta > 0) else 0
        return jnp.full((B,), n, jnp.int32)
    if impl == "ref":
        return _ref.nlj_count(x, y, theta)
    Bp, bm = _grid_dim(B, 256, 8)
    Np, bn = _grid_dim(N, 512, 128)
    dp, bk = _grid_dim(d, 512, 128)
    xp = _pad_axis(_pad_rows(x, Bp), dp, axis=1)
    # Padded data rows: shift them far away so they never match. Padding the
    # *vector* with a huge coordinate inflates ‖y‖² to ~1e60 ≫ θ².
    yp = _pad_axis(_pad_rows(y, Np, fill=1e30), dp, axis=1)
    out = _nlj.nlj_count_pallas(xp, yp, float(theta), bm=bm, bn=bn, bk=bk,
                                interpret=(impl == "pallas_interpret"))
    return out[:B, 0]


def nlj_mask(x: Array, y: Array, *, theta: float,
             impl: str | None = None, metric: str = "l2") -> Array:
    """Exact boolean match matrix (B, N) — via pairwise kernel + compare."""
    from repro.core.types import theta_key
    d = pairwise_dists(x, y, metric=metric, impl=impl)
    return d < theta_key(theta, metric)


def topk_merge(beam_dist: Array, beam_idx: Array, cand_dist: Array,
               cand_idx: Array, *, impl: str | None = None
               ) -> tuple[Array, Array]:
    """Merge sorted beam with candidates; keep L smallest.

    The one path on every backend is ``xla``: ``lax.top_k`` over the
    concatenated row, which keeps the ``ref`` argsort's stable order
    (equal distances keep their position order) without sorting the
    whole row. ``ref`` is the oracle."""
    impl = _resolve("topk_merge", impl or "xla")
    if impl == "ref":
        return _ref.topk_merge(beam_dist, beam_idx, cand_dist, cand_idx)
    if impl != "xla":
        raise ValueError(f"topk_merge has no {impl!r} implementation")
    L = beam_dist.shape[-1]
    alld = jnp.concatenate([beam_dist, cand_dist], axis=-1)
    alli = jnp.concatenate([beam_idx, cand_idx], axis=-1)
    neg, pos = jax.lax.top_k(-alld, L)
    return -neg, jnp.take_along_axis(alli, pos, axis=-1)


@functools.partial(jax.jit, static_argnames=("impl",))
def gather_sq_dists(vecs: Array, x: Array, idx: Array, *,
                    impl: str | None = None) -> Array:
    """(N,d) vecs × (B,d) queries × (B,K) ids → (B,K) f32 sq dists.

    NO_NODE (-1) slots come back +inf. The Pallas path fuses the gather
    with the distance (ids scalar-prefetched; see kernels/gather_distance).
    """
    impl = _resolve("gather_sq_dists", impl)
    B, K = idx.shape
    if B == 0 or K == 0:
        return jnp.zeros((B, K), jnp.float32)
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0)
    if impl == "ref":
        d = _ref.rowwise_sq_dists(x, vecs[safe])
    else:
        Bp = _round_up(B, _gd.ROWS)
        xp, ip = _pad_rows(x, Bp), _pad_rows(safe, Bp)
        d = jnp.concatenate([
            _gd.gather_sq_dists_pallas(
                vecs, xp[r0:r1], ip[r0:r1],
                interpret=(impl == "pallas_interpret"))
            for r0, r1 in _prefetch_chunks(Bp, K)])[:B]
    return jnp.where(valid, d, jnp.float32(jnp.inf))


# ids per scalar-prefetched gather call: 128 KiB of int32, well inside the
# v5e's 1 MiB SMEM (a (256, 1024) id matrix in one call overflows it)
_PREFETCH_IDS = 1 << 15


def _prefetch_chunks(B: int, K: int) -> list[tuple[int, int]]:
    """Row ranges of a (B, K) id matrix, B a multiple of 8, such that each
    range's ids fit one scalar-prefetch buffer."""
    step = max(_gd.ROWS, _PREFETCH_IDS // max(K, 1) // _gd.ROWS * _gd.ROWS)
    return [(r0, min(r0 + step, B)) for r0 in range(0, B, step)]


# ---------------------------------------------------------------------------
# band compaction — sparse re-rank over a boolean band mask
# ---------------------------------------------------------------------------


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def grow_cap(cur: int, needed: int, limit: int) -> int:
    """The one capacity-growth rule for band-compaction overflow retries
    (single-device ``waves.RerankCap`` and the sharded driver share it):
    next power of two covering the observed band, never shrinking,
    clamped to the pool width."""
    return min(max(next_pow2(needed), cur), limit)


def band_compact(mask: Array, ids: Array, cap: int
                 ) -> tuple[Array, Array, Array]:
    """Stably compact masked slots of a (B, C) id matrix into ``cap`` slots.

    The re-rank front door: the cascade's ambiguous band is a sparse
    subset of the pool, but the gather kernel wants a dense id matrix.
    A ``cumsum`` over the mask assigns each masked slot its rank within
    the lane (stable: pool order is preserved), slots beyond ``cap``
    fall into a discarded sink column.

    Returns ``(slots, cand, n_masked)``:
      * ``slots``   (B, cap) int32 — source column of each compacted
        entry, −1 for unused capacity;
      * ``cand``    (B, cap) int32 — ``ids`` gathered through ``slots``
        (−1, i.e. NO_NODE, where unused) — feed straight into
        ``gather_sq_dists``;
      * ``n_masked`` (B,) int32 — band occupancy per lane. Entries with
        rank ≥ cap are *not* compacted (overflow = n_masked − cap);
        callers must detect ``n_masked > cap`` and retry at a larger
        capacity to keep results exact.
    """
    B, C = mask.shape
    pos = jnp.cumsum(mask, axis=1) - 1                     # rank within lane
    within = mask & (pos < cap)
    tgt = jnp.where(within, pos, cap)                      # sink = cap
    lane = jnp.arange(B, dtype=jnp.int32)[:, None]
    col = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None, :], (B, C))
    slots = jnp.full((B, cap + 1), -1, jnp.int32)
    slots = slots.at[lane, tgt].set(jnp.where(within, col, -1))[:, :cap]
    cand = jnp.where(slots >= 0,
                     jnp.take_along_axis(ids, jnp.clip(slots, 0), axis=1),
                     -1)
    return slots, cand, jnp.sum(mask, axis=1).astype(jnp.int32)


def band_scatter(slots: Array, vals: Array, C: int, fill=jnp.inf) -> Array:
    """Inverse of ``band_compact``: scatter (B, cap) compacted values back
    to their (B, C) source columns; unused slots read ``fill``."""
    B, cap = slots.shape
    lane = jnp.arange(B, dtype=jnp.int32)[:, None]
    tgt = jnp.where(slots >= 0, slots, C)                  # sink = C
    out = jnp.full((B, C + 1), fill, vals.dtype)
    return out.at[lane, tgt].set(
        jnp.where(slots >= 0, vals, jnp.asarray(fill, vals.dtype)))[:, :C]


def compact_gather_sq_dists(vecs: Array, x: Array, ids: Array, mask: Array,
                            cap: int, *, impl: str | None = None
                            ) -> tuple[Array, Array, Array]:
    """Exact f32 distances for the masked slots of a pooled id matrix,
    computed through a ``cap``-wide compacted gather.

    Returns ``(exact, within, n_masked)``: ``exact`` is (B, C) with the
    true squared distance on every compacted masked slot and +inf
    elsewhere; ``within`` marks the masked slots that actually got
    re-ranked (rank < cap). The gather kernel only ever sees
    ``B × cap`` ids — traffic scales with the band, not the pool."""
    C = ids.shape[1]
    slots, cand, n_masked = band_compact(mask, ids, cap)
    exact_c = gather_sq_dists(vecs, x, cand, impl=impl)
    exact = band_scatter(slots, exact_c, C)
    pos = jnp.cumsum(mask, axis=1) - 1
    within = mask & (pos < cap)
    return exact, within, n_masked


# ---------------------------------------------------------------------------
# int8 (QuantStore) kernels
# ---------------------------------------------------------------------------


def _pad_quant_dims(q: Array, scales: Array, group_size: int
                    ) -> tuple[Array, Array]:
    """Pad the dim axis to a whole number of groups (zero codes, unit
    scales — padded dims contribute exactly 0 to every distance)."""
    d = q.shape[-1]
    dp = _round_up(max(d, 1), group_size)
    q = _pad_axis(q, dp, axis=q.ndim - 1)
    G = dp // group_size
    scales = _pad_rows(scales.reshape(-1, 1).astype(jnp.float32), G,
                       fill=1.0)[:, 0]
    return q, scales


def _dequant_norms(q: Array, scales: Array, group_size: int) -> Array:
    """(N,) f32 squared norms of the dequantized rows, from codes."""
    deq = _ref._dequant(q, scales, group_size)
    return jnp.sum(deq * deq, axis=-1)


@functools.partial(jax.jit, static_argnames=("group_size", "impl"))
def pairwise_sq_dists_int8(qx: Array, qy: Array, scales: Array, *,
                           group_size: int = 128,
                           xn: Array | None = None, yn: Array | None = None,
                           impl: str | None = None) -> Array:
    """(B, d) × (N, d) int8 → (B, N) f32 *quantized-domain* squared L2.

    ``qx``/``qy`` must share the scale grid (queries quantized via
    ``quant.store.quantize_queries``). ``xn``/``yn`` are the dequantized
    squared norms; pass the QuantStore's stored norms to skip recompute.
    """
    impl = _resolve("pairwise_sq_dists_int8", impl)
    B, d = qx.shape
    N, _ = qy.shape
    if B == 0 or N == 0 or d == 0:
        return jnp.zeros((B, N), jnp.float32)
    if impl == "ref":
        return _ref.pairwise_sq_dists_int8(qx, qy, scales,
                                           group_size=group_size)
    if xn is None:
        xn = _dequant_norms(qx, scales, group_size)
    if yn is None:
        yn = _dequant_norms(qy, scales, group_size)
    qxp, sp = _pad_quant_dims(qx, scales, group_size)
    qyp, _ = _pad_quant_dims(qy, scales, group_size)
    Bp, bm = _grid_dim(B, 256, 32)
    Np, bn = _grid_dim(N, 512, 128)
    qxp = _pad_rows(qxp, Bp)
    qyp = _pad_rows(qyp, Np)
    xnp = _pad_rows(xn.reshape(B, 1), Bp)[:, 0]
    ynp = _pad_rows(yn.reshape(N, 1), Np)[:, 0]
    out = _int8.pairwise_sq_dists_int8_pallas(
        qxp, qyp, sp, xnp, ynp, bm=bm, bn=bn, group_size=group_size,
        interpret=(impl == "pallas_interpret"))
    return out[:B, :N]


@functools.partial(jax.jit, static_argnames=("group_size", "impl"))
def rowwise_sq_dists_int8(qx: Array, qcands: Array, scales: Array, *,
                          group_size: int = 128,
                          impl: str | None = None) -> Array:
    """(B, d) × (B, K, d) int8 → (B, K) f32 quantized-domain squared L2.

    Difference form — exact on a shared scale grid; the kernel moves d×1
    bytes per candidate instead of the f32 path's d×4.
    """
    impl = _resolve("rowwise_sq_dists_int8", impl)
    B, d = qx.shape
    _, K, _ = qcands.shape
    if B == 0 or K == 0 or d == 0:
        return jnp.zeros((B, K), jnp.float32)
    if impl == "ref":
        return _ref.rowwise_sq_dists_int8(qx, qcands, scales,
                                          group_size=group_size)
    qxp, sp = _pad_quant_dims(qx, scales, group_size)
    qcp, _ = _pad_quant_dims(qcands, scales, group_size)
    Bp, bm = _grid_dim(B, 32, 32)
    Kp, bkk = _grid_dim(K, 128, 128)
    qxp = _pad_rows(qxp, Bp)
    qcp = _pad_axis(_pad_rows(qcp, Bp), Kp, axis=1)
    out = _int8.rowwise_sq_dists_int8_pallas(
        qxp, qcp, sp, bm=bm, bkk=bkk, group_size=group_size,
        interpret=(impl == "pallas_interpret"))
    return out[:B, :K]


# ---------------------------------------------------------------------------
# PDX (dimension-partitioned) early-exit kernels
# ---------------------------------------------------------------------------


def _pdx_guards(dim: int) -> tuple[float, float]:
    """(relative, absolute) tail-bound deflation for dim ``dim`` —
    lazy import keeps kernels free of quant-package dependencies."""
    from repro.quant.pdx import TAIL_GUARD, tail_guard
    return tail_guard(dim), TAIL_GUARD


@functools.partial(jax.jit,
                   static_argnames=("slab", "dim", "early_exit", "impl"))
def pairwise_sq_dists_pdx(qx: Array, qy: Array, scales: Array,
                          xslab: Array, yslab: Array, xtail: Array,
                          ytail: Array, xn: Array, yn: Array, xe: Array,
                          ye: Array, theta, *, slab: int, dim: int,
                          early_exit: bool = False,
                          impl: str | None = None) -> tuple[Array, Array]:
    """PDX early-exit quantized pairwise distances (the NLJ tier shape).

    (B, S·slab) × (N, S·slab) int8 PDX codes → ``(dhat, nscan)``:
    (B, N) f32 quantized-domain squared L2 (+inf where a lane retired on
    its certified tail bound) and (B, N) int32 slabs scanned per lane.
    ``theta`` is the traced L2 threshold; with ``early_exit=False`` the
    kernel is a plain slab-ordered accumulation (``nscan`` = S) whose
    survivor sums are bit-identical to the early-exit run's.
    """
    impl = _resolve("pairwise_sq_dists_pdx", impl)
    B = qx.shape[0]
    N = qy.shape[0]
    if B == 0 or N == 0:
        return (jnp.zeros((B, N), jnp.float32), jnp.zeros((B, N), jnp.int32))
    if impl == "ref":
        return _ref.pairwise_sq_dists_pdx(
            qx, qy, scales, xslab, yslab, xtail, ytail, xn, yn, xe, ye,
            theta, slab=slab, dim=dim, early_exit=early_exit)
    guard, guard_abs = _pdx_guards(dim)
    from repro.quant.cascade import MATMUL_GUARD
    S = scales.shape[0]
    Bp, bm = _grid_dim(B, 256, 32)
    Np, bn = _grid_dim(N, 512, 128)
    dhat, nscan = _pdx.pairwise_sq_dists_pdx_pallas(
        _pad_rows(qx, Bp), _pad_rows(qy, Np), scales,
        _pad_rows(xslab, Bp), _pad_rows(yslab, Np),
        _pad_rows(xtail, Bp), _pad_rows(ytail, Np),
        _pad_rows(xn.reshape(B, 1), Bp)[:, 0],
        _pad_rows(yn.reshape(N, 1), Np)[:, 0],
        _pad_rows(xe.reshape(B, 1), Bp)[:, 0],
        _pad_rows(ye.reshape(N, 1), Np)[:, 0],
        theta, guard=guard, guard_abs=guard_abs, mguard=MATMUL_GUARD,
        early_exit=early_exit, bm=bm, bn=bn,
        interpret=(impl == "pallas_interpret"))
    return dhat[:B, :N], nscan[:B, :N]


@functools.partial(jax.jit, static_argnames=("dim", "early_exit", "impl"))
def pdx_gather_sq_dists(vp: Array, vtail: Array, vnorm: Array, xp: Array,
                        xtail: Array, xn: Array, idx: Array, th2, *,
                        dim: int, early_exit: bool = False,
                        impl: str | None = None) -> tuple[Array, Array]:
    """Fused PDX gather + early-exit f32 distance over candidate ids.

    (N, S·slab) PDX rows × (B, S·slab) PDX queries × (B, K) ids →
    ``(dist, nscan)``. NO_NODE (−1) slots come back (+inf, 0). ``th2``
    is the traced θ² retirement threshold; retired lanes are +inf, and
    survivors carry the slab-ordered f32 sum (bit-identical on/off).
    """
    impl = _resolve("pdx_gather_sq_dists", impl)
    B, K = idx.shape
    if B == 0 or K == 0:
        return (jnp.zeros((B, K), jnp.float32), jnp.zeros((B, K), jnp.int32))
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0)
    S = vtail.shape[1]
    slab = vp.shape[1] // S
    if impl == "ref":
        d, ns = _ref.pdx_gather_sq_dists(
            xp, xtail, xn, vp[safe], vtail[safe], vnorm[safe], th2,
            slab=slab, dim=dim, early_exit=early_exit)
    else:
        guard, guard_abs = _pdx_guards(dim)
        Bp = _round_up(B, _gd.ROWS)
        xp, xtail, xn, safe_p = (_pad_rows(a, Bp)
                                 for a in (xp, xtail, xn, safe))
        parts = [_pdx.pdx_gather_sq_dists_pallas(
            vp, vtail, vnorm, xp[r0:r1], xtail[r0:r1], xn[r0:r1],
            safe_p[r0:r1], th2, guard=guard, guard_abs=guard_abs,
            early_exit=early_exit, interpret=(impl == "pallas_interpret"))
            for r0, r1 in _prefetch_chunks(Bp, K)]
        d = jnp.concatenate([p[0] for p in parts])[:B]
        ns = jnp.concatenate([p[1] for p in parts])[:B]
    return (jnp.where(valid, d, jnp.float32(jnp.inf)),
            jnp.where(valid, ns, 0))


def pdx_compact_gather_sq_dists(vp: Array, vtail: Array, vnorm: Array,
                                xp: Array, xtail: Array, xn: Array,
                                ids: Array, mask: Array, cap: int, th2, *,
                                dim: int, early_exit: bool = False,
                                impl: str | None = None):
    """PDX twin of ``compact_gather_sq_dists``: early-exit re-rank of the
    masked band slots through a ``cap``-wide compacted gather.

    Returns ``(exact, within, n_masked, n_scanned, n_total)`` — the
    first three as in the f32 version (``exact`` is +inf on retired
    *and* uncompacted slots), plus scalar dimension-scan counters for
    ``JoinStats.dims_scanned_frac`` (over compacted valid lanes only).
    """
    C = ids.shape[1]
    slots, cand, n_masked = band_compact(mask, ids, cap)
    dist_c, nscan_c = pdx_gather_sq_dists(
        vp, vtail, vnorm, xp, xtail, xn, cand, th2, dim=dim,
        early_exit=early_exit, impl=impl)
    exact = band_scatter(slots, dist_c, C)
    pos = jnp.cumsum(mask, axis=1) - 1
    within = mask & (pos < cap)
    S = vtail.shape[1]
    slab = vp.shape[1] // S
    valid = cand >= 0
    dims = jnp.minimum(nscan_c * slab, dim)
    n_scanned = jnp.sum(jnp.where(valid, dims, 0))
    n_total = jnp.sum(valid.astype(jnp.int32)) * dim
    return exact, within, n_masked, n_scanned, n_total


# ---------------------------------------------------------------------------
# 1-bit sketch (Hamming) kernels — the tier above int8
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("impl",))
def pairwise_hamming(cx: Array, cy: Array, *, impl: str | None = None
                     ) -> Array:
    """(B, W) × (N, W) uint32 sketch codes → (B, N) int32 Hamming counts.

    Counts convert to certified L2 lower bounds via the per-vector slack
    tables (``quant.sketch.sketch_lower_bound_pairwise``)."""
    impl = _resolve("pairwise_hamming", impl)
    B, W = cx.shape
    N, _ = cy.shape
    if B == 0 or N == 0 or W == 0:
        return jnp.zeros((B, N), jnp.int32)
    if impl == "ref":
        return _ref.pairwise_hamming(cx, cy)
    Bp, bm = _grid_dim(B, 128, 8)
    Np, bn = _grid_dim(N, 128, 8)
    cxp = _pad_rows(cx, Bp)
    cyp = _pad_rows(cy, Np)
    out = _bits.pairwise_hamming_pallas(
        cxp, cyp, bm=bm, bn=bn, interpret=(impl == "pallas_interpret"))
    return out[:B, :N]


@functools.partial(jax.jit, static_argnames=("impl",))
def rowwise_hamming(cx: Array, ccands: Array, *, impl: str | None = None
                    ) -> Array:
    """(B, W) × (B, K, W) uint32 → (B, K) int32 Hamming counts over
    per-query gathered candidate codes (the traversal's shape)."""
    impl = _resolve("rowwise_hamming", impl)
    B, W = cx.shape
    _, K, _ = ccands.shape
    if B == 0 or K == 0 or W == 0:
        return jnp.zeros((B, K), jnp.int32)
    if impl == "ref":
        return _ref.rowwise_hamming(cx, ccands)
    Bp, bm = _grid_dim(B, 8, 8)
    Kp, bkk = _grid_dim(K, 128, 128)
    cxp = _pad_rows(cx, Bp)
    ccp = _pad_axis(_pad_rows(ccands, Bp), Kp, axis=1)
    out = _bits.rowwise_hamming_pallas(
        cxp, ccp, bm=bm, bkk=bkk, interpret=(impl == "pallas_interpret"))
    return out[:B, :K]


# ---------------------------------------------------------------------------
# quantization error → certified distance bounds (shared helper)
# ---------------------------------------------------------------------------


def quant_lower_bound(d_hat: Array, slack: Array) -> Array:
    """Certified lower bound on the true squared distance.

    ``d_hat`` is the quantized-domain squared distance ``‖x̂ − ŷ‖²``;
    ``slack`` is the per-pair L2 slack ``‖x−x̂‖ + ‖y−ŷ‖`` (exact errors,
    not bounds). By the triangle inequality
    ``‖x−y‖ ≥ ‖x̂−ŷ‖ − slack``, so a threshold test
    ``quant_lower_bound(d̂, s) < θ²`` accepts every pair the exact test
    accepts — the filter side of filter-then-rerank. +inf d_hat stays
    +inf (masked candidates)."""
    lb = jnp.maximum(jnp.sqrt(jnp.maximum(d_hat, 0.0)) - slack, 0.0)
    return jnp.where(jnp.isfinite(d_hat), lb * lb, d_hat)


def quant_upper_bound(d_hat: Array, slack: Array) -> Array:
    """Certified upper bound on the true squared distance (symmetric to
    ``quant_lower_bound``; used by tests and early-accept heuristics)."""
    ub = jnp.sqrt(jnp.maximum(d_hat, 0.0)) + slack
    return jnp.where(jnp.isfinite(d_hat), ub * ub, d_hat)


def quant_band_from_lb(lb: Array, slack: Array, th2) -> tuple[Array, Array]:
    """Partition lower-bound-filtered candidates into (sure, ambiguous).

    ``lb`` is a certified lower bound (``quant_lower_bound`` output,
    e.g. the traversal's pooled distances); ``slack`` the per-pair L2
    slack. Since ``√lb + 2·slack ≥ √d̂ + slack``, the matching upper
    bound is ``quant_upper_bound(lb, 2·slack)`` — looser only where the
    lower bound was clamped to 0, which stays sound. ``sure`` entries
    are certified true pairs (no re-rank needed); ``ambiguous`` entries
    need the exact kernel. The single source of the band arithmetic for
    the host, shard_map, and NLJ re-rank paths."""
    ub = quant_upper_bound(lb, 2.0 * slack)
    sure = ub < th2
    return sure, ~sure
