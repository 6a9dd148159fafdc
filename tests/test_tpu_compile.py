"""The join path's Pallas kernels compile for a described TPU v5e.

Interpret mode (the rest of the suite) does not check what the TPU
compiler checks: block shapes aligned to the (8, 128) tile, SMEM/VMEM
placement, fast-memory limits. Each test here lowers one ``kernels.ops``
entry with ``impl="pallas"`` for one chip of a described ``v5e:2x2``
(nothing runs; no chip is needed) at the SIFT width d=128, the T2I width
d=200 and the GIST width d=960, and asserts the compiled program holds the kernel
(``tpu_custom_call``) under the stable name its ``pallas_call`` gives it,
the name a profiler trace shows for its device op.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import traversal
from repro.core.types import GraphIndex, TraversalConfig
from repro.kernels import ops
from repro.quant.pdx import DEFAULT_SLAB

B, N, K = 256, 8192, 128
# widest id matrix a wave hands the gathers: the band capacity may grow to
# the pool width (TraversalConfig.pool_cap), and the ids are prefetched
# into SMEM
POOL = 1024
f32, i8, i32, u32 = jnp.float32, jnp.int8, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    # the pinned JAX ships the TPU compiler: a failure here is a broken
    # install, and must show up as one
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    compile for a described device is written to the cache but cannot be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _f32_pair(S, d):
    return (S((B, d), f32), S((N, d), f32)), {}


def _gather(S, d):
    return (S((N, d), f32), S((B, d), f32), S((B, POOL), i32)), {}


def _int8_pairwise(S, d):
    return (S((B, d), i8), S((N, d), i8), S((-(-d // 128),), f32)), {}


def _int8_rowwise(S, d):
    return (S((B, d), i8), S((B, K, d), i8), S((-(-d // 128),), f32)), {}


def _pdx_pairwise(S, d, slab=DEFAULT_SLAB):
    ns = -(-d // slab)
    dp = ns * slab
    args = (S((B, dp), i8), S((N, dp), i8), S((ns,), f32), S((B, ns), f32),
            S((N, ns), f32), S((B, ns), f32), S((N, ns), f32), S((B,), f32),
            S((N,), f32), S((B,), f32), S((N,), f32), S((), f32))
    return args, dict(slab=slab, dim=d, early_exit=True)


def _pdx_gather(S, d, slab=DEFAULT_SLAB):
    ns = -(-d // slab)
    dp = ns * slab
    args = (S((N, dp), f32), S((N, ns), f32), S((N,), f32), S((B, dp), f32),
            S((B, ns), f32), S((B,), f32), S((B, POOL), i32), S((), f32))
    return args, dict(dim=d, early_exit=True)


def _hamming_pairwise(S, d):
    w = -(-d // 32)
    return (S((B, w), u32), S((N, w), u32)), {}


def _hamming_rowwise(S, d):
    w = -(-d // 32)
    return (S((B, w), u32), S((B, K, w), u32)), {}


KERNELS = {
    "pairwise_sq_dists": (ops.pairwise_sq_dists, _f32_pair),
    "rowwise_sq_dists": (ops.rowwise_sq_dists,
                         lambda S, d: ((S((B, d), f32), S((B, K, d), f32)),
                                       {})),
    "pairwise_neg_ip": (ops.pairwise_neg_ip, _f32_pair),
    "rowwise_neg_ip": (ops.rowwise_neg_ip,
                       lambda S, d: ((S((B, d), f32), S((B, K, d), f32)),
                                     {})),
    "nlj_count": (ops.nlj_count,
                  lambda S, d: (_f32_pair(S, d)[0], dict(theta=1.0))),
    "gather_sq_dists": (ops.gather_sq_dists, _gather),
    "pairwise_sq_dists_int8": (ops.pairwise_sq_dists_int8, _int8_pairwise),
    "rowwise_sq_dists_int8": (ops.rowwise_sq_dists_int8, _int8_rowwise),
    "pairwise_sq_dists_pdx": (ops.pairwise_sq_dists_pdx, _pdx_pairwise),
    "pdx_gather_sq_dists": (ops.pdx_gather_sq_dists, _pdx_gather),
    # slab 128: each slab is its own lane-aligned block (no masking)
    "pairwise_sq_dists_pdx_slab128": (
        ops.pairwise_sq_dists_pdx,
        lambda S, d: _pdx_pairwise(S, d, slab=128)),
    "pdx_gather_sq_dists_slab128": (
        ops.pdx_gather_sq_dists, lambda S, d: _pdx_gather(S, d, slab=128)),
    "pairwise_hamming": (ops.pairwise_hamming, _hamming_pairwise),
    "rowwise_hamming": (ops.rowwise_hamming, _hamming_rowwise),
}


@pytest.mark.parametrize("d", [128, 200, 960])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name, d):
    fn, shapes = KERNELS[name]

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args, kw = shapes(S, d)
    text = fn.lower(*args, impl="pallas", **kw).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls, (name, d)
    kernel = name.removesuffix("_slab128")
    assert all(re.search(rf"%{kernel}(\.\d+)? = ", ln) for ln in calls), (
        name, d, [ln.split(" = ")[0] for ln in calls])


def _wide_gathers_scatters(text, n_idx):
    """``(op, dtype, scopes)`` of every gather and scatter in a compiled
    HLO module that takes ``n_idx`` indices. A scatter inside a fusion
    carries no metadata of its own; its scopes are then those named
    anywhere in its fused computation."""
    blocks, cur = [], None
    for ln in text.splitlines():
        if cur is None:
            if ln.endswith("{") and not ln.startswith(" "):
                cur = []
        elif ln.startswith("}"):
            blocks.append(cur)
            cur = None
        else:
            cur.append(ln)
    found = []
    for blk in blocks:
        shapes = {m[1]: [int(v) for v in m[2].split(",") if v]
                  for m in (re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+"
                                     r"\[([\d,]*)\]", ln) for ln in blk)
                  if m}
        names = re.findall(r'op_name="([^"]*)"', "\n".join(blk))
        for ln in blk:
            m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[[\d,]*\]\S* "
                         r"(gather|scatter)\(%[\w.\-]+, %([\w.\-]+)", ln)
            if not m:
                continue
            dims = shapes[m[3]]
            ivd = int(re.search(r"index_vector_dim=(\d+)", ln)[1])
            if math.prod(dims) // (dims[ivd] if ivd < len(dims) else 1) \
                    != n_idx:
                continue
            own = re.search(r'op_name="([^"]*)"', ln)
            found.append((m[2], m[1], [own[1]] if own else names))
    return found


def test_range_expand_dedup_sorts_without_gather_or_scatter(one_chip):
    """The BFS loop's in-batch dedup is two row sorts: under ``visited``
    the only gather and scatter that take all B·K candidate slots are the
    bitmap's word lookup and its update (u32), and no B·K-wide flag
    scatter (``pred``) is left anywhere in the compiled loop."""
    B, K, d, R = 256, 128, 128, 32
    cfg = TraversalConfig()
    assert cfg.expand_per_iter * R == K

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    index = GraphIndex(vecs=S((N, d), f32), nbrs=S((N, R), i32),
                       start=S((), i32), mean_nbr_dist=S((N,), f32),
                       n_data=N)
    text = traversal.range_expand.lower(
        index, S((B, d), f32), S((), f32), cfg=cfg, n_data=N,
        hybrid=False, traverse_nondata=True, init_idx=S((B, R), i32),
        init_dist=S((B, R), f32), init_valid=S((B, R), jnp.bool_),
        visited=S((B, traversal.bitmap_words(N)), u32),
        best_dist=S((B,), f32), best_idx=S((B,), i32),
        n_dist=S((B,), i32)).compile().as_text()
    assert not re.search(rf"= pred\[{B * K}\]\S* scatter\(", text)
    wide = _wide_gathers_scatters(text, B * K)
    in_visited = sorted((op, dtype) for op, dtype, scopes in wide
                        if any("/while/body/visited/" in s for s in scopes))
    assert in_visited == [("gather", "u32"), ("scatter", "u32")], wide
