"""The join path's Pallas kernels compile for a described TPU v5e.

Interpret mode (the rest of the suite) does not check what the TPU
compiler checks: block shapes aligned to the (8, 128) tile, SMEM/VMEM
placement, fast-memory limits. Each test here lowers one ``kernels.ops``
entry with ``impl="pallas"`` for one chip of a described ``v5e:2x2``
(nothing runs; no chip is needed) at the SIFT width d=128 and the GIST
width d=960, and asserts the compiled program holds the kernel
(``tpu_custom_call``) under the stable name its ``pallas_call`` gives it,
the name a profiler trace shows for its device op.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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


@pytest.mark.parametrize("d", [128, 960])
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
