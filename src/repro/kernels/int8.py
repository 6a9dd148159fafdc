"""Pallas TPU kernels for quantized (int8) squared-L2 distances.

Companions of the f32 kernels in ``distance.py``, operating on QuantStore
codes (per-dimension-group scaled int8; see ``repro.quant.store``). Both
kernels step the k-grid one *dimension group* at a time, so the per-group
dequantization scale is a scalar read per step (the (G,) scale vector
lives whole in SMEM — a (1, 1) VMEM block is not a legal TPU tile) and
the inner arithmetic stays in the integer domain:

  * ``pairwise`` — int8×int8 ``dot_general`` accumulating in int32 (the
    MXU's native int8 path), dequantized per group into the f32 output
    block; the epilogue applies the matmul identity with the stored f32
    norms of the *dequantized* vectors, so the result is exactly
    ``‖x̂ − ŷ‖²`` up to f32 rounding.
  * ``rowwise``  — per-query gathered candidates in the difference form:
    int8 widened to int32, squared differences reduced per group in int32
    (≤ 254²·group_size ≈ 8.3e6 ≪ 2³¹ — no overflow), scaled into the f32
    accumulator. Valid because queries are quantized on the same scale
    grid as the store.

Both compute the *quantized-domain* distance d̂ = ‖x̂ − ŷ‖². Certified
bounds on the true distance come from the per-vector exact errors via
``ops.quant_lower_bound`` (triangle inequality), outside the kernels.

Bytes moved per distance drop from d×4 (f32) to d×1 — the compression
lever this subsystem exists for; int8 min-tile on TPU is (32, 128), which
the default block shapes respect.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


# ---------------------------------------------------------------------------
# pairwise: (B, d) x (N, d) int8 -> (B, N) f32 quantized squared L2
# ---------------------------------------------------------------------------

def _pairwise_i8_kernel(x_ref, y_ref, s_ref, xn_ref, yn_ref, o_ref, *,
                        nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jax.lax.dot_general(
        x_ref[...], y_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)          # int8×int8 → int32 (MXU)
    s = s_ref[k]
    o_ref[...] += (s * s) * acc.astype(jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        d = xn_ref[...] + yn_ref[...] - 2.0 * o_ref[...]
        o_ref[...] = jnp.maximum(d, 0.0)


def pairwise_sq_dists_int8_pallas(qx: Array, qy: Array, scales: Array,
                                  xn: Array, yn: Array, *, bm: int = 256,
                                  bn: int = 512, group_size: int = 128,
                                  interpret: bool = False) -> Array:
    """Tiled quantized pairwise squared-L2 ``‖x̂ − ŷ‖²``.

    Args:
      qx: (B, d) int8; qy: (N, d) int8 — same scale grid.
      scales: (G,) f32, one per dimension group; d == G * group_size.
      xn/yn: (B,) / (N,) f32 squared norms of the dequantized rows.
    Shapes must already be block-divisible (ops.py pads).
    """
    B, d = qx.shape
    N, _ = qy.shape
    bm, bn = min(bm, B), min(bn, N)
    nk = d // group_size
    assert B % bm == 0 and N % bn == 0 and d % group_size == 0, (
        qx.shape, qy.shape, (bm, bn, group_size))
    assert scales.shape == (nk,), (scales.shape, nk)
    grid = (B // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_pairwise_i8_kernel, nk=nk),
        name="pairwise_sq_dists_int8",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, group_size), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, group_size), lambda i, j, k: (j, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
    )(qx, qy, scales, xn.reshape(B, 1), yn.reshape(1, N))


# ---------------------------------------------------------------------------
# rowwise: (B, d) x (B, K, d) int8 -> (B, K) f32 quantized squared L2
# ---------------------------------------------------------------------------

def _rowwise_i8_kernel(x_ref, c_ref, s_ref, o_ref):
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    xb = x_ref[...].astype(jnp.int32)              # (bm, gs)
    cb = c_ref[...].astype(jnp.int32)              # (bm, bkk, gs)
    diff = cb - xb[:, None, :]
    ssq = jnp.sum(diff * diff, axis=-1)            # int32, no overflow
    s = s_ref[kd]
    o_ref[...] += (s * s) * ssq.astype(jnp.float32)


def rowwise_sq_dists_int8_pallas(qx: Array, qcands: Array, scales: Array, *,
                                 bm: int = 32, bkk: int = 128,
                                 group_size: int = 128,
                                 interpret: bool = False) -> Array:
    """Tiled quantized per-query candidate distances (difference form)."""
    B, d = qx.shape
    _, K, _ = qcands.shape
    bm, bkk = min(bm, B), min(bkk, K)
    nk = d // group_size
    assert B % bm == 0 and K % bkk == 0 and d % group_size == 0, (
        qx.shape, qcands.shape, (bm, bkk, group_size))
    assert scales.shape == (nk,), (scales.shape, nk)
    grid = (B // bm, K // bkk, nk)
    return pl.pallas_call(
        _rowwise_i8_kernel,
        name="rowwise_sq_dists_int8",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, group_size), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bkk, group_size), lambda i, j, k: (i, j, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bkk), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        interpret=interpret,
    )(qx, qcands, scales)
