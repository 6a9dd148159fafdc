"""Sharding-rule tests on the (abstract) production mesh — no devices
needed: specs are validated structurally for all 10 archs."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get
from repro.models import model as M
from repro.models import sharding as S

MESHES = {
    "single": jax.sharding.AbstractMesh((16, 16), ("data", "model")),
    "multi": jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}


def _axes_size(mesh, axes):
    shape = dict(mesh.shape)
    if axes is None:
        return 1
    if isinstance(axes, str):
        return shape[axes]
    return int(np.prod([shape[a] for a in axes]))


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divide_everywhere(arch, mesh_name):
    mesh = MESHES[mesh_name]
    mc = get(arch).model
    pshape = jax.eval_shape(lambda k: M.init_params(k, mc),
                            jax.random.key(0))
    specs = S.param_specs(pshape, mesh)
    flat_p = jax.tree.leaves(pshape)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    sharded_bytes = 0
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= leaf.ndim
        denom = 1
        for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            sz = _axes_size(mesh, axes)
            assert dim % sz == 0, (arch, leaf.shape, spec)
            denom *= sz
        sharded_bytes += int(np.prod(leaf.shape)) * leaf.dtype.itemsize \
            // denom
    # params must actually fit per device (16 GB v5e) with room to spare
    assert sharded_bytes < 8e9, (arch, sharded_bytes)


@pytest.mark.parametrize("arch", ["llama3_405b", "qwen3_moe_235b_a22b"])
def test_big_weights_are_sharded(arch):
    """No multi-GB leaf may end up replicated."""
    mesh = MESHES["single"]
    mc = get(arch).model
    pshape = jax.eval_shape(lambda k: M.init_params(k, mc),
                            jax.random.key(0))
    specs = S.param_specs(pshape, mesh)
    for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(pshape)[0],
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if nbytes > 1e9:
            assert any(a is not None for a in spec), (path, leaf.shape)


def test_moe_experts_on_model_axis():
    mesh = MESHES["single"]
    mc = get("qwen3_moe_235b_a22b").model
    pshape = jax.eval_shape(lambda k: M.init_params(k, mc),
                            jax.random.key(0))
    specs = S.param_specs(pshape, mesh)
    gate_spec = specs["layers"][0]["ffn"]["gate"]
    assert tuple(gate_spec)[:2] == (None, "model")   # (G, E, d, f): E → EP


def test_divisibility_fallback():
    """hubert's 504-vocab head cannot shard 16 ways — falls to replication
    on that dim instead of erroring."""
    mesh = MESHES["single"]
    mc = get("hubert_xlarge").model
    pshape = jax.eval_shape(lambda k: M.init_params(k, mc),
                            jax.random.key(0))
    specs = S.param_specs(pshape, mesh)
    head = tuple(specs["head"])
    assert head[-1] is None          # 504 % 16 != 0 ⇒ replicated vocab dim


def test_cache_specs_batch_vs_sequence_sharding():
    mesh = MESHES["single"]
    mc = get("h2o_danube_3_4b").model
    cshape = jax.eval_shape(lambda: M.init_caches(mc, 128, 1024))
    specs = S.cache_specs(cshape, mesh, batch=128)
    k_spec = tuple(specs[0]["k"])        # (G, B, W, K, hd)
    assert k_spec[1] == "data" and k_spec[2] == "model"
    # batch=1: sequence dim takes all axes
    specs1 = S.cache_specs(jax.eval_shape(
        lambda: M.init_caches(mc, 1, 4096)), mesh, batch=1)
    k1 = tuple(specs1[0]["k"])
    assert k1[1] is None and k1[2] == ("data", "model")
