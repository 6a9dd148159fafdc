"""Fused exact nested-loop-join (NLJ) Pallas kernel.

The paper's exact baseline (§2.2.1) and the ground-truth generator. Rather
than materializing the full (B, N) distance matrix in HBM and comparing in a
second pass, this kernel fuses distance + threshold compare + per-query match
count in VMEM: the (bm, bn) distance tile never leaves the core. The only
HBM traffic is the operands and a (B, 1) count vector — i.e., the kernel is
pure MXU roofline (2·B·N·d FLOPs over (B+N)·d bytes).

The count output block is revisited across both the N-tile and d-tile grid
dims (reduction accumulation), which requires those grid dims to be
"arbitrary" (sequential) — the B-tile dim stays parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance import f32_precision

Array = jax.Array


def _nlj_kernel(x_ref, y_ref, xn_ref, yn_ref, cnt_ref, acc_ref, *,
                nk: int, theta_sq: float):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((j == 0) & (k == 0))
    def _zero_cnt():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=f32_precision(x_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        d = xn_ref[...] + yn_ref[...] - 2.0 * acc_ref[...]
        hits = (d < theta_sq).astype(jnp.int32)
        cnt_ref[...] += jnp.sum(hits, axis=1, keepdims=True)


def nlj_count_pallas(x: Array, y: Array, theta: float, *, bm: int = 256,
                     bn: int = 512, bk: int = 512,
                     interpret: bool = False) -> Array:
    """Exact per-query join counts, fused in VMEM.

    Args:
      x: (B, d) queries; y: (N, d) data — block-divisible shapes (ops.py pads;
        padded y rows must carry +inf norms, handled by the wrapper).
      theta: L2 threshold (not squared).
    Returns:
      (B, 1) int32 counts.
    """
    B, d = x.shape
    N, _ = y.shape
    bm, bn, bk = min(bm, B), min(bn, N), min(bk, d)
    assert B % bm == 0 and N % bn == 0 and d % bk == 0
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=-1, keepdims=True)
    yn = jnp.sum(yf * yf, axis=-1, keepdims=True).T
    nk = d // bk
    grid = (B // bm, N // bn, nk)
    kernel = functools.partial(_nlj_kernel, nk=nk,
                               theta_sq=float(theta) ** 2)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    return pl.pallas_call(
        kernel,
        name="nlj_count",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, y, xn, yn)
