"""Pallas TPU kernels for the join's distance-computation hot spot (paper C4).

Two kernels:

  * ``pairwise``  — queries (B, d) vs a *shared* data tile (N, d) in the
    matmul form ``‖x‖² + ‖y‖² − 2·x·yᵀ``. This is MXU-shaped: arithmetic
    intensity grows with d, so it runs compute-bound for the paper's
    embedding dims (128–960). Used by the exact NLJ baseline and by the
    offline kNN-graph build.

  * ``rowwise``   — queries (B, d) vs *per-query gathered* candidates
    (B, K, d) from the graph traversal. Each candidate row is used exactly
    once ⇒ memory-bound VPU work; the kernel tiles (B, K, d) so the working
    set sits in VMEM and the d-reduction accumulates in the f32 output block.

Block shapes default to MXU/VPU-aligned (multiples of 8×128 for f32);
wrappers in ops.py pad and slice. Both kernels accumulate in f32 regardless
of input dtype. Reduction accumulates into the revisited output block
(standard Pallas matmul pattern), so no scratch is required.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def f32_precision(dtype):
    """Contraction precision for the matmul-form distance kernels: f32
    operands contract at full f32 (``HIGHEST``), because the certified
    bounds and the exact NLJ assume f32 rounding and the TPU's default
    may contract f32 at bf16 precision. Narrower inputs keep the default:
    their products are exact in the f32 accumulator (and Mosaic rejects
    ``HIGHEST`` for them)."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else None)


# ---------------------------------------------------------------------------
# pairwise: (B, d) x (N, d) -> (B, N) squared L2, matmul form
# ---------------------------------------------------------------------------

def _pairwise_kernel(x_ref, y_ref, xn_ref, yn_ref, o_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=f32_precision(x_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        d = xn_ref[...] + yn_ref[...] - 2.0 * o_ref[...]
        o_ref[...] = jnp.maximum(d, 0.0)


def pairwise_sq_dists_pallas(x: Array, y: Array, *, bm: int = 256,
                             bn: int = 512, bk: int = 512,
                             interpret: bool = False) -> Array:
    """Tiled pairwise squared-L2. Shapes must already be block-divisible.

    Args:
      x: (B, d); y: (N, d). B % bm == 0, N % bn == 0, d % bk == 0.
    Returns:
      (B, N) f32 squared distances.
    """
    B, d = x.shape
    N, _ = y.shape
    bm, bn, bk = min(bm, B), min(bn, N), min(bk, d)
    assert B % bm == 0 and N % bn == 0 and d % bk == 0, (x.shape, y.shape, (bm, bn, bk))
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=-1, keepdims=True)          # (B, 1)
    yn = jnp.sum(yf * yf, axis=-1, keepdims=True).T        # (1, N)
    nk = d // bk
    grid = (B // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_pairwise_kernel, nk=nk),
        name="pairwise_sq_dists",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
    )(x, y, xn, yn)


# ---------------------------------------------------------------------------
# rowwise: (B, d) x (B, K, d) -> (B, K) squared L2 over gathered candidates
# ---------------------------------------------------------------------------

def _rowwise_kernel(x_ref, c_ref, o_ref, *, nd: int):
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    xb = x_ref[...].astype(jnp.float32)          # (bm, dk)
    cb = c_ref[...].astype(jnp.float32)          # (bm, bkk, dk)
    diff = cb - xb[:, None, :]
    o_ref[...] += jnp.sum(diff * diff, axis=-1)


def rowwise_sq_dists_pallas(x: Array, cands: Array, *, bm: int = 8,
                            bkk: int = 128, dk: int = 512,
                            interpret: bool = False) -> Array:
    """Tiled per-query candidate distances. Shapes must be block-divisible."""
    B, d = x.shape
    _, K, _ = cands.shape
    bm, bkk, dk = min(bm, B), min(bkk, K), min(dk, d)
    assert B % bm == 0 and K % bkk == 0 and d % dk == 0
    nd = d // dk
    grid = (B // bm, K // bkk, nd)
    return pl.pallas_call(
        functools.partial(_rowwise_kernel, nd=nd),
        name="rowwise_sq_dists",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, dk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bkk, dk), lambda i, j, k: (i, j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bkk), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        interpret=interpret,
    )(x, cands)
