"""Pallas TPU kernels for PDX (dimension-partitioned) early-exit
squared-L2 distances.

Both kernels step the k-grid one *dimension slab* at a time over
vectors stored in the PDX layout (``repro.quant.pdx``: dims permuted by
descending variance, padded to ``S·slab``). The distance accumulates
slab by slab in the f32 output block, and — when ``early_exit`` is on —
a lane is *retired* at the start of slab ``k`` if its partial sum plus
the certified remaining-dims lower bound already exceeds the lane's
threshold:

    live_k = (scanned == k) & (acc + tail_k ≤ th)

``scanned`` is a second output block acting as a per-lane latch: a lane
that fails the predicate once keeps ``scanned < k`` forever, so later
slabs skip it for free and the final ``scanned`` value *is* the number
of slabs scanned (``JoinStats.dims_scanned_frac``). The epilogue masks
retired lanes to ``+inf``; survivors hold the slab-ordered f32 sum,
bit-identical to the ``early_exit=False`` accumulation (same
contributions, same order — f32 round-to-nearest of nonnegative adds is
deterministic), which is what makes the on/off pair sets provably equal.

The tail bound is ``max((√tx(k) − √ty(k))² − guard·(xn+yn) − guard_abs,
0)`` — reverse triangle inequality on the per-row suffix energies,
deflated by the f32 rounding allowance (``pdx.tail_guard``), so
retirement certifies the full f32 sum would exceed the threshold.

  * ``pairwise`` — int8 codes on the per-slab grid; the slab
    contribution uses the matmul identity with per-slab dequantized
    energies as norms, ``max(·, 0)``-clamped so partial sums are
    monotone (the clamp's inflation is covered by the caller's
    ``MATMUL_GUARD``). The per-lane threshold
    ``(θ + xe + ye)² + MATMUL_GUARD·(xn + yn)`` bakes the quantization
    slack in, so retirement implies the *certified lower bound* on the
    true distance exceeds θ².
  * ``gather``   — f32 rows via scalar-prefetch (the band re-rank
    shape, replacing the full-``d`` gather of ``gather_distance.py``);
    per-lane ``@pl.when(live)`` skips the whole DMA'd-row reduction for
    retired lanes.

TPU tiling: a block's last two dims must be multiples of (8, 128) or
span the whole array. A slab that is a multiple of 128 lanes (or the
only slab) is its own legal block. A narrower slab (the default 64) is
scanned through a full-width block: the pairwise kernel takes a per-slab
copy of the queries with every other slab's codes zeroed (an int8 dot
over exact zeros adds exactly 0), and the gather kernel masks the
squared differences to slab ``k``'s lanes. That keeps the results exact
but not the work: each slab step does a full-width dot or reduction, so
a lane that scans all ``S`` slabs costs ``S`` times one full pass (at
slab 128 a step does one slab's work). Per-slab tables are taken
whole along ``S`` and the column for slab ``k`` is picked with a one-hot
mask (exact); scales and the threshold are scalars in SMEM; the gather
kernel reads 8-row blocks and selects its row, as in
``gather_distance.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_distance import ROWS, put_slot, select_row

Array = jax.Array


def lane_aligned(slab: int, n_slab: int) -> bool:
    """Whether one slab is a legal TPU block on its own (a multiple of
    the 128-lane tile, or the whole row)."""
    return slab % 128 == 0 or n_slab == 1


def _pick(block: Array, axis: int, k) -> Array:
    """Entry ``k`` along ``axis`` of a 2-D block (keepdims), by one-hot
    masking — exact, and no dynamic lane or sublane slicing."""
    pos = jax.lax.broadcasted_iota(jnp.int32, block.shape, axis)
    return jnp.sum(jnp.where(pos == k, block, 0), axis=axis, keepdims=True)


def _pick2(block: Array, r, c) -> Array:
    """Entry (r, c) of a 2-D block as a (1, 1) array (exact)."""
    return _pick(_pick(block, 0, r), 1, c)


# ---------------------------------------------------------------------------
# pairwise: int8 PDX codes -> (B, N) f32 quantized sq L2 + slabs scanned
# ---------------------------------------------------------------------------

def _pairwise_pdx_kernel(x_ref, y_ref, s_ref, xsl_ref, ysl_ref, xtl_ref,
                         ytl_ref, xn_ref, yn_ref, xe_ref, ye_ref, th_ref,
                         o_ref, ns_ref, *, nk: int, guard: float,
                         guard_abs: float, mguard: float, early_exit: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)
        ns_ref[...] = jnp.zeros_like(ns_ref)

    def _contrib():
        dot = jax.lax.dot_general(
            x_ref[...], y_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)      # int8×int8 → int32 (MXU)
        s = s_ref[k]
        c = (_pick(xsl_ref[...], 1, k) + _pick(ysl_ref[...], 0, k)
             - 2.0 * (s * s) * dot.astype(jnp.float32))
        return jnp.maximum(c, 0.0)                 # monotone partial sums

    if not early_exit:
        o_ref[...] += _contrib()

        @pl.when(k == nk - 1)
        def _done():
            ns_ref[...] = jnp.full_like(ns_ref, nk)
        return

    energy = xn_ref[...] + yn_ref[...]                       # (bm, bn)
    th = ((th_ref[0] + xe_ref[...] + ye_ref[...]) ** 2
          + jnp.float32(mguard) * energy)
    rt = (jnp.sqrt(_pick(xtl_ref[...], 1, k))
          - jnp.sqrt(_pick(ytl_ref[...], 0, k))) ** 2
    tl = jnp.maximum(rt - jnp.float32(guard) * energy
                     - jnp.float32(guard_abs), 0.0)
    acc = o_ref[...]
    scanned = ns_ref[...]
    live = (scanned == k) & (acc + tl <= th)

    @pl.when(jnp.any(live))
    def _scan():
        o_ref[...] = jnp.where(live, acc + _contrib(), acc)
        ns_ref[...] = jnp.where(live, k + 1, scanned)

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = jnp.where(ns_ref[...] == nk, o_ref[...], jnp.inf)


def pairwise_sq_dists_pdx_pallas(qx: Array, qy: Array, scales: Array,
                                 xslab: Array, yslab: Array, xtail: Array,
                                 ytail: Array, xn: Array, yn: Array,
                                 xe: Array, ye: Array, theta, *,
                                 guard: float, guard_abs: float,
                                 mguard: float, early_exit: bool,
                                 bm: int = 256, bn: int = 512,
                                 interpret: bool = False):
    """Tiled PDX early-exit quantized pairwise squared L2.

    Args:
      qx/qy: (B, S·slab) / (N, S·slab) int8 codes, same per-slab grid.
      scales: (S,) f32; xslab/yslab, xtail/ytail: (B, S) / (N, S) f32
        per-slab dequantized energies and suffix energies.
      xn/yn, xe/ye: (B,) / (N,) f32 norms and exact quant errors.
      theta: traced f32 L2 threshold (unsquared).
    Returns:
      (dhat, nscan): (B, N) f32 (+inf where retired), (B, N) int32.
    Shapes must already be block-divisible (ops.py pads).
    """
    B, dp = qx.shape
    N, _ = qy.shape
    S = scales.shape[0]
    slab = dp // S
    bm, bn = min(bm, B), min(bn, N)
    assert B % bm == 0 and N % bn == 0 and dp == S * slab, (
        qx.shape, qy.shape, (bm, bn, S))
    grid = (B // bm, N // bn, S)
    kernel = functools.partial(
        _pairwise_pdx_kernel, nk=S, guard=guard, guard_abs=guard_abs,
        mguard=mguard, early_exit=early_exit)
    if lane_aligned(slab, S):
        xs = qx
        x_spec = pl.BlockSpec((bm, slab), lambda i, j, k: (i, k))
        y_spec = pl.BlockSpec((bn, slab), lambda i, j, k: (j, k))
    else:
        # one copy of the query codes per slab, zero outside it
        own = (jnp.arange(dp)[None, :] // slab) == jnp.arange(S)[:, None]
        xs = jnp.where(own[:, None, :], qx[None], jnp.int8(0))
        x_spec = pl.BlockSpec((None, bm, dp), lambda i, j, k: (k, i, 0))
        y_spec = pl.BlockSpec((bn, dp), lambda i, j, k: (j, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        name="pairwise_sq_dists_pdx",
        grid=grid,
        in_specs=[
            x_spec,
            y_spec,
            smem,
            pl.BlockSpec((bm, S), lambda i, j, k: (i, 0)),
            pl.BlockSpec((S, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, S), lambda i, j, k: (i, 0)),
            pl.BlockSpec((S, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, N), jnp.float32),
            jax.ShapeDtypeStruct((B, N), jnp.int32),
        ],
        interpret=interpret,
    )(xs, qy, scales, xslab, yslab.T, xtail, ytail.T,
      xn.reshape(B, 1), yn.reshape(1, N), xe.reshape(B, 1),
      ye.reshape(1, N), jnp.asarray(theta, jnp.float32).reshape(1))


# ---------------------------------------------------------------------------
# gather: f32 PDX rows via scalar prefetch -> (B, K) f32 + slabs scanned
# ---------------------------------------------------------------------------

def _gather_pdx_kernel(idx_ref, x_ref, xtl_ref, xn_ref, v_ref, vtl_ref,
                       vn_ref, th_ref, o_ref, ns_ref, *, nk: int, slab: int,
                       masked: bool, guard: float, guard_abs: float,
                       early_exit: bool):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    r = i % ROWS                     # query row within its 8-row block
    rv = idx_ref[i, j] % ROWS        # candidate row within its block

    @pl.when(k == 0)
    def _zero():
        put_slot(o_ref, r, j, jnp.zeros((1, 1), jnp.float32))
        put_slot(ns_ref, r, j, jnp.zeros((1, 1), jnp.int32))

    def _contrib():
        diff = select_row(x_ref[...], r) - select_row(v_ref[...], rv)
        sq = diff * diff
        if masked:                   # full-width block: slab k's lanes
            lane = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 1)
            sq = jnp.where(lane // slab == k, sq, 0.0)
        return jnp.sum(sq, axis=-1, keepdims=True)            # (1, 1)

    acc = _pick2(o_ref[...], r, j)
    scanned = _pick2(ns_ref[...], r, j)
    if not early_exit:
        put_slot(o_ref, r, j, acc + _contrib())

        @pl.when(k == nk - 1)
        def _done():
            put_slot(ns_ref, r, j, jnp.full((1, 1), nk, jnp.int32))
        return

    energy = _pick(xn_ref[...], 0, r) + _pick(vn_ref[...], 0, rv)
    rt = (jnp.sqrt(_pick2(xtl_ref[...], r, k))
          - jnp.sqrt(_pick2(vtl_ref[...], rv, k))) ** 2
    tl = jnp.maximum(rt - jnp.float32(guard) * energy
                     - jnp.float32(guard_abs), 0.0)
    live = (scanned == k) & (acc + tl <= th_ref[0])

    @pl.when(jnp.any(live))          # retired lane: skip the reduction
    def _scan():
        put_slot(o_ref, r, j, acc + _contrib())
        put_slot(ns_ref, r, j, jnp.full((1, 1), k + 1, jnp.int32))

    @pl.when(k == nk - 1)
    def _epilogue():
        done = _pick2(ns_ref[...], r, j) == nk
        put_slot(o_ref, r, j,
                 jnp.where(done, _pick2(o_ref[...], r, j), jnp.inf))


def pdx_gather_sq_dists_pallas(vp: Array, vtail: Array, vnorm: Array,
                               xp: Array, xtail: Array, xn: Array,
                               idx: Array, th2, *, guard: float,
                               guard_abs: float, early_exit: bool,
                               interpret: bool = False):
    """Fused PDX gather + early-exit distance (scalar prefetch).

    Args:
      vp: (N, S·slab) f32 PDX rows; vtail: (N, S); vnorm: (N,).
      xp: (B, S·slab) f32 PDX queries; xtail: (B, S); xn: (B,).
      idx: (B, K) int32 ids, pre-clamped to [0, N) by the wrapper.
      th2: traced f32 θ² retirement threshold.
    B must be a multiple of 8 (the ops.py wrapper pads).
    Returns:
      (dist, nscan): (B, K) f32 (+inf where retired), (B, K) int32.
    """
    B, dp = xp.shape
    _, K = idx.shape
    N, S = vtail.shape
    slab = dp // S
    assert B % ROWS == 0, (B, ROWS)
    masked = not lane_aligned(slab, S)
    w = dp if masked else slab
    kernel = functools.partial(
        _gather_pdx_kernel, nk=S, slab=slab, masked=masked, guard=guard,
        guard_abs=guard_abs, early_exit=early_exit)

    def lanes(k):
        return 0 if masked else k

    def own(i, j, k, idx_ref):
        return (i // ROWS, 0)

    def cand(i, j, k, idx_ref):
        return (idx_ref[i, j] // ROWS, 0)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, S),
        in_specs=[
            pl.BlockSpec((ROWS, w),
                         lambda i, j, k, idx_ref: (i // ROWS, lanes(k))),
            pl.BlockSpec((ROWS, S), own),
            pl.BlockSpec((ROWS, 1), own),
            pl.BlockSpec((ROWS, w), lambda i, j, k, idx_ref: (
                idx_ref[i, j] // ROWS, lanes(k))),
            pl.BlockSpec((ROWS, S), cand),
            pl.BlockSpec((ROWS, 1), cand),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, K), own),
            pl.BlockSpec((ROWS, K), own),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="pdx_gather_sq_dists",
        grid_spec=spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, K), jnp.float32),
            jax.ShapeDtypeStruct((B, K), jnp.int32),
        ],
        interpret=interpret,
    )(idx, xp, xtail, xn.reshape(B, 1), vp, vtail, vnorm.reshape(N, 1),
      jnp.asarray(th2, jnp.float32).reshape(1))
