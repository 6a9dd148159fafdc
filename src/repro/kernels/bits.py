"""Pallas TPU kernels for 1-bit sketch (Hamming) distances.

Companions of the f32 kernels in ``distance.py`` and the int8 kernels in
``int8.py``, operating on SketchStore codes (packed sign bits, 32 dims
per uint32 lane; see ``repro.quant.sketch``). Both kernels XOR the packed
words and reduce a SWAR popcount on the VPU — pure integer element-wise
work, no MXU:

  * ``pairwise`` — (B, W) × (N, W) → (B, N) int32 Hamming counts;
  * ``rowwise``  — (B, W) × (B, K, W) → (B, K) int32 counts over
    per-query gathered candidate codes (the traversal's shape).

The word axis is small (W = ⌈d/32⌉ ≤ 64 even at d = 2048), so blocks
carry it whole — no k-grid, no accumulator initialization. Bytes moved
per distance drop from d×4 (f32) or d×1 (int8) to d/8: the cheapest tier
of the progressive-refinement cascade. Hamming counts convert to
certified L2 lower bounds *outside* the kernels via the per-vector slack
tables (``sketch.sketch_lower_bound_*``).

The SWAR popcount uses only shifts/masks/adds (no multiply), all native
VPU ops on uint32 lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _popcount(v: Array) -> Array:
    """Per-element bit count of a uint32 array (SWAR, shift-add form)."""
    m1 = jnp.uint32(0x55555555)
    m2 = jnp.uint32(0x33333333)
    m4 = jnp.uint32(0x0F0F0F0F)
    v = v - ((v >> 1) & m1)
    v = (v & m2) + ((v >> 2) & m2)
    v = (v + (v >> 4)) & m4
    v = v + (v >> 8)
    v = v + (v >> 16)
    return (v & jnp.uint32(0x3F)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# pairwise: (B, W) x (N, W) uint32 -> (B, N) int32 Hamming
# ---------------------------------------------------------------------------

def _pairwise_hamming_kernel(x_ref, y_ref, o_ref):
    x = x_ref[...]                                  # (bm, W) uint32
    y = y_ref[...]                                  # (bn, W) uint32
    v = x[:, None, :] ^ y[None, :, :]               # (bm, bn, W)
    o_ref[...] = jnp.sum(_popcount(v), axis=-1)


def pairwise_hamming_pallas(cx: Array, cy: Array, *, bm: int = 128,
                            bn: int = 128,
                            interpret: bool = False) -> Array:
    """Tiled pairwise Hamming distance between packed sign-bit codes.

    Shapes must already be block-divisible (ops.py pads); padded rows
    carry zero codes and their counts are sliced away by the wrapper.
    """
    B, W = cx.shape
    N, _ = cy.shape
    bm, bn = min(bm, B), min(bn, N)
    assert B % bm == 0 and N % bn == 0, (cx.shape, cy.shape, (bm, bn))
    grid = (B // bm, N // bn)
    return pl.pallas_call(
        _pairwise_hamming_kernel,
        name="pairwise_hamming",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, W), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, W), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.int32),
        interpret=interpret,
    )(cx, cy)


# ---------------------------------------------------------------------------
# rowwise: (B, W) x (B, K, W) uint32 -> (B, K) int32 Hamming
# ---------------------------------------------------------------------------

def _rowwise_hamming_kernel(x_ref, c_ref, o_ref):
    x = x_ref[...]                                  # (bm, W)
    c = c_ref[...]                                  # (bm, bkk, W)
    v = c ^ x[:, None, :]
    o_ref[...] = jnp.sum(_popcount(v), axis=-1)


def rowwise_hamming_pallas(cx: Array, ccands: Array, *, bm: int = 8,
                           bkk: int = 128,
                           interpret: bool = False) -> Array:
    """Tiled per-query Hamming distance over gathered candidate codes."""
    B, W = cx.shape
    _, K, _ = ccands.shape
    bm, bkk = min(bm, B), min(bkk, K)
    assert B % bm == 0 and K % bkk == 0, (cx.shape, ccands.shape, (bm, bkk))
    grid = (B // bm, K // bkk)
    return pl.pallas_call(
        _rowwise_hamming_kernel,
        name="rowwise_hamming",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, W), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bkk, W), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bkk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.int32),
        interpret=interpret,
    )(cx, ccands)
