"""Pure-jnp reference oracles for every Pallas kernel.

These are the semantic ground truth: each kernel in this package must be
allclose to the corresponding function here across shape/dtype sweeps
(see tests/test_kernels.py). They are also the default implementation on
CPU hosts, where Pallas runs in interpret mode (slow).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def pairwise_sq_dists(x: Array, y: Array) -> Array:
    """Squared L2 distances between all rows of x and y.

    Args:
      x: (B, d) queries.
      y: (N, d) data.
    Returns:
      (B, N) float32 squared distances.
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    xn = jnp.sum(x * x, axis=-1, keepdims=True)          # (B, 1)
    yn = jnp.sum(y * y, axis=-1, keepdims=True).T        # (1, N)
    xy = jnp.matmul(x, y.T,                              # (B, N)
                    precision=jax.lax.Precision.HIGHEST)
    d = xn + yn - 2.0 * xy
    return jnp.maximum(d, 0.0)


def rowwise_sq_dists(x: Array, cands: Array) -> Array:
    """Squared L2 distance between each query and its own candidate rows.

    Args:
      x: (B, d) queries.
      cands: (B, K, d) per-query gathered candidate vectors.
    Returns:
      (B, K) float32 squared distances.
    """
    x = x.astype(jnp.float32)
    cands = cands.astype(jnp.float32)
    diff = cands - x[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def nlj_count(x: Array, y: Array, theta: float) -> Array:
    """Exact nested-loop-join matched-pair count per query.

    Returns (B,) int32: |{j : dist(x_b, y_j) < theta}| (theta on L2, not
    squared — callers pass the paper's thresholds directly).
    """
    d = pairwise_sq_dists(x, y)
    return jnp.sum(d < jnp.float32(theta) ** 2, axis=-1).astype(jnp.int32)


def nlj_mask(x: Array, y: Array, theta: float) -> Array:
    """Exact nested-loop-join boolean match matrix (B, N)."""
    d = pairwise_sq_dists(x, y)
    return d < jnp.float32(theta) ** 2


def _dequant(q: Array, scales: Array, group_size: int) -> Array:
    """int8 codes on a per-dimension-group scale grid → f32 vectors
    (delegates to the store's single dequantization definition)."""
    from repro.quant.store import dequantize
    return dequantize(q, scales, group_size)


def pairwise_sq_dists_int8(qx: Array, qy: Array, scales: Array, *,
                           group_size: int = 128) -> Array:
    """Quantized-domain pairwise squared L2: ``‖x̂ − ŷ‖²`` via dequantize.

    The Pallas kernel computes the same quantity in the int domain
    (int8×int8 dots scaled per group); both equal the true distance
    between the *dequantized* vectors up to f32 rounding.
    """
    return pairwise_sq_dists(_dequant(qx, scales, group_size),
                             _dequant(qy, scales, group_size))


def rowwise_sq_dists_int8(qx: Array, qcands: Array, scales: Array, *,
                          group_size: int = 128) -> Array:
    """Quantized-domain rowwise squared L2 over gathered candidates."""
    return rowwise_sq_dists(_dequant(qx, scales, group_size),
                            _dequant(qcands, scales, group_size))


def pairwise_hamming(cx: Array, cy: Array) -> Array:
    """Pairwise Hamming distance between packed sign-bit sketch codes.

    Args:
      cx: (B, W) uint32 query codes; cy: (N, W) uint32 data codes.
    Returns:
      (B, N) int32 differing-bit counts.
    """
    pc = jax.lax.population_count(cx[:, None, :] ^ cy[None, :, :])
    return jnp.sum(pc.astype(jnp.int32), axis=-1)


def rowwise_hamming(cx: Array, ccands: Array) -> Array:
    """Per-query Hamming distance over gathered candidate codes.

    Args:
      cx: (B, W) uint32 query codes; ccands: (B, K, W) uint32.
    Returns:
      (B, K) int32 differing-bit counts.
    """
    pc = jax.lax.population_count(ccands ^ cx[:, None, :])
    return jnp.sum(pc.astype(jnp.int32), axis=-1)


def _pdx_live_loop(slab_contribs, tails, th, nk: int, early_exit: bool):
    """Shared slab-ordered accumulation with per-lane retirement latch.

    ``slab_contribs[k]`` is the (lane-shaped) f32 contribution of slab k;
    ``tails[k]`` the certified (deflated) remaining-dims lower bound at
    the *start* of slab k; ``th`` the per-lane retirement threshold.
    Returns ``(acc, nscan)``: retired lanes report ``+inf`` and the slab
    index at which they retired; survivors report the slab-ordered f32
    sum (bit-identical to the ``early_exit=False`` accumulation, which
    adds the same contributions in the same order).
    """
    acc = jnp.zeros_like(slab_contribs[0])
    if not early_exit:
        for k in range(nk):
            acc = acc + slab_contribs[k]
        return acc, jnp.full(acc.shape, nk, jnp.int32)
    scanned = jnp.zeros(acc.shape, jnp.int32)
    for k in range(nk):
        live = (scanned == k) & (acc + tails[k] <= th)
        acc = jnp.where(live, acc + slab_contribs[k], acc)
        scanned = jnp.where(live, k + 1, scanned)
    acc = jnp.where(scanned == nk, acc, jnp.inf)
    return acc, scanned


def pairwise_sq_dists_pdx(qx: Array, qy: Array, scales: Array,
                          xslab: Array, yslab: Array, xtail: Array,
                          ytail: Array, xn: Array, yn: Array, xe: Array,
                          ye: Array, theta, *, slab: int, dim: int,
                          early_exit: bool) -> tuple[Array, Array]:
    """PDX early-exit quantized pairwise squared L2 (the NLJ tier shape).

    Args:
      qx/qy: (B, S·slab) / (N, S·slab) int8 codes on the per-slab grid.
      scales: (S,) f32 per-slab dequant scales.
      xslab/yslab: (B, S) / (N, S) f32 per-slab dequantized energies.
      xtail/ytail: (B, S) / (N, S) f32 dequantized suffix energies.
      xn/yn: (B,) / (N,) f32 dequantized squared norms.
      xe/ye: (B,) / (N,) f32 exact per-row quantization errors.
      theta: L2 threshold (unsquared); per-lane retirement threshold is
        ``(θ + xe + ye)² + MATMUL_GUARD·(xn + yn)`` so retirement implies
        the *certified lower bound* on the true distance exceeds θ².
    Returns:
      (dhat, nscan): (B, N) f32 quantized distances (+inf where retired)
      and (B, N) int32 slabs scanned per lane.
    """
    from repro.quant.cascade import MATMUL_GUARD
    from repro.quant.pdx import deflate_tail
    nk = scales.shape[0]
    x32 = qx.astype(jnp.int32)
    y32 = qy.astype(jnp.int32)
    energy = xn[:, None] + yn[None, :]
    th = ((jnp.float32(theta) + xe[:, None] + ye[None, :]) ** 2
          + jnp.float32(MATMUL_GUARD) * energy)
    contribs, tails = [], []
    for k in range(nk):
        dot = x32[:, k * slab:(k + 1) * slab] @ y32[:, k * slab:(k + 1) * slab].T
        s = scales[k]
        c = (xslab[:, k][:, None] + yslab[:, k][None, :]
             - 2.0 * (s * s) * dot.astype(jnp.float32))
        contribs.append(jnp.maximum(c, 0.0))
        rt = (jnp.sqrt(xtail[:, k])[:, None]
              - jnp.sqrt(ytail[:, k])[None, :]) ** 2
        tails.append(deflate_tail(rt, energy, dim))
    return _pdx_live_loop(contribs, tails, th, nk, early_exit)


def pdx_gather_sq_dists(xp: Array, xtail: Array, xn: Array, vcand: Array,
                        vtail: Array, vnorm: Array, th2, *, slab: int,
                        dim: int, early_exit: bool) -> tuple[Array, Array]:
    """PDX early-exit f32 rowwise squared L2 over gathered candidates
    (the re-rank band shape).

    Args:
      xp: (B, S·slab) f32 permuted, padded queries.
      xtail: (B, S) f32 query suffix energies; xn: (B,) squared norms.
      vcand: (B, K, S·slab) f32 gathered candidate rows (PDX layout).
      vtail: (B, K, S) f32 candidate suffix energies; vnorm: (B, K).
      th2: θ² retirement threshold (f32 domain — the tail deflation
        covers slab-sum rounding, so retirement implies the full
        slab-ordered f32 sum would exceed θ²).
    Returns:
      (dist, nscan): (B, K) f32 (+inf where retired) and int32 slabs
      scanned.
    """
    from repro.quant.pdx import deflate_tail
    nk = xtail.shape[1]
    energy = xn[:, None] + vnorm
    th = jnp.broadcast_to(jnp.float32(th2), energy.shape)
    contribs, tails = [], []
    for k in range(nk):
        diff = vcand[:, :, k * slab:(k + 1) * slab] \
            - xp[:, None, k * slab:(k + 1) * slab]
        contribs.append(jnp.sum(diff * diff, axis=-1))
        rt = (jnp.sqrt(xtail[:, k])[:, None] - jnp.sqrt(vtail[:, :, k])) ** 2
        tails.append(deflate_tail(rt, energy, dim))
    return _pdx_live_loop(contribs, tails, th, nk, early_exit)


def topk_merge(beam_dist: Array, beam_idx: Array, cand_dist: Array,
               cand_idx: Array) -> tuple[Array, Array]:
    """Merge a sorted beam with new candidates, keep the L smallest.

    Args:
      beam_dist/beam_idx: (B, L) current beam (ascending by dist).
      cand_dist/cand_idx: (B, K) new candidates (any order; +inf = invalid).
    Returns:
      (B, L) merged beam, ascending.
    """
    L = beam_dist.shape[-1]
    alld = jnp.concatenate([beam_dist, cand_dist], axis=-1)
    alli = jnp.concatenate([beam_idx, cand_idx], axis=-1)
    order = jnp.argsort(alld, axis=-1)
    alld = jnp.take_along_axis(alld, order, axis=-1)
    alli = jnp.take_along_axis(alli, order, axis=-1)
    return alld[:, :L], alli[:, :L]
