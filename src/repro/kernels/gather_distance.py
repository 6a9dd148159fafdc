"""Fused neighbor-gather + distance Pallas kernel (scalar prefetch).

The traversal inner loop's hot spot (paper C4): given gathered candidate
ids per query, compute squared L2 distances query→candidate. A naive
implementation gathers candidate rows to HBM first (vecs[idx] materializes
(B, K, d)) and then runs a rowwise-distance pass — 2× the HBM traffic.

This kernel uses Pallas *scalar prefetch*: the (B, K) index matrix is
prefetched to SMEM, and each grid step's BlockSpec index_map picks the
candidate's row block of ``vecs`` directly — the rows are DMA'd HBM→VMEM
and consumed in-register; the gathered matrix never exists in HBM.

TPU tiling: a block's last two dims must be multiples of (8, 128) or span
the whole array, so a single (1, d) row is not a legal block. Each grid
step instead DMAs the aligned 8-row block holding the candidate
(``idx // 8``) and selects row ``idx % 8`` with a one-hot mask — adding
exact zeros, so the distance is the same f32 sum as a one-row reduction.
The d axis is taken whole (legal for any d, 100 and 960 included). The
query row is selected from its 8-row block the same way, and results land
in an (8, K) output block written back once per 8 queries. The price: each
candidate moves an 8-row block of ``vecs`` (8·d·4 bytes in f32), 8× one
row, and each step
rewrites the whole (8, K) block in VMEM to set one slot. Rows past the
table's end in its last, partial block are never selected. Invalid ids
(NO_NODE) must be pre-clamped to 0 by the wrapper and masked afterwards.

This kernel is also the back end of the band-compacted re-rank
(``ops.compact_gather_sq_dists``): the wave pipeline compacts the
cascade's ambiguous band into a fixed small capacity and hands the
compacted (B, cap) id matrix here, so K is the band capacity rather than
the pool width — the scalar-prefetch index_map then DMAs only band rows.
Unused capacity arrives as clamped id 0; the wrapper masks those slots to
+inf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

ROWS = 8      # sublane tile: rows per DMA'd block


def select_row(block: Array, r) -> Array:
    """Row ``r`` of an (8, w) block as (1, w), by one-hot masking — exact
    (the other rows contribute +0.0) and free of dynamic sublane slicing."""
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(rows == r, block, 0.0), axis=0, keepdims=True)


def put_slot(o_ref, r, j, val) -> None:
    """Write scalar-shaped ``val`` (1, 1) into slot (r, j) of an (8, K)
    output block, leaving the other slots untouched."""
    rows = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] = jnp.where((rows == r) & (cols == j),
                           val.astype(o_ref.dtype), o_ref[...])


def _kernel(idx_ref, x_ref, v_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    x = select_row(x_ref[...].astype(jnp.float32), i % ROWS)      # (1, d)
    v = select_row(v_ref[...].astype(jnp.float32),
                   idx_ref[i, j] % ROWS)                          # (1, d)
    diff = x - v
    put_slot(o_ref, i % ROWS, j, jnp.sum(diff * diff, axis=-1, keepdims=True))


def gather_sq_dists_pallas(vecs: Array, x: Array, idx: Array, *,
                           interpret: bool = False) -> Array:
    """(N, d) vecs, (B, d) queries, (B, K) int32 ids → (B, K) f32 dists.

    ids must already be clamped to [0, N) and B must be a multiple of 8;
    the ops.py wrapper pads B and masks NO_NODE slots with +inf afterwards.
    """
    B, d = x.shape
    _, K = idx.shape
    assert B % ROWS == 0, (B, ROWS)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K),
        in_specs=[
            pl.BlockSpec((ROWS, d), lambda i, j, idx_ref: (i // ROWS, 0)),
            pl.BlockSpec((ROWS, d),
                         lambda i, j, idx_ref: (idx_ref[i, j] // ROWS, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, K),
                               lambda i, j, idx_ref: (i // ROWS, 0)),
    )
    return pl.pallas_call(
        _kernel,
        name="gather_sq_dists",
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        interpret=interpret,
    )(idx, x, vecs)
