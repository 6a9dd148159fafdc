"""Optimizer + gradient-compression tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adafactor, adamw
from repro.optim.compress import (_dequantize, _quantize, flatten_grads,
                                  unflatten_grads)


def _quadratic_params():
    return dict(w=jnp.asarray(np.linspace(-2, 2, 64), jnp.float32),
                b=jnp.zeros((8,), jnp.float32))


def _loss(params):
    return jnp.sum(params["w"] ** 2) + jnp.sum((params["b"] - 1.0) ** 2)


@pytest.mark.parametrize("make_opt", [
    lambda: adamw(weight_decay=0.0),
    lambda: adamw(weight_decay=0.0, moment_dtype=jnp.bfloat16),
    lambda: adafactor(),
])
def test_optimizer_descends(make_opt):
    opt = make_opt()
    params = _quadratic_params()
    state = opt.init(params)
    losses = []
    for _ in range(60):
        g = jax.grad(_loss)(params)
        params, state = opt.update(g, state, params, jnp.float32(0.05))
        losses.append(float(_loss(params)))
    assert losses[-1] < 0.05 * losses[0]


def test_adamw_bf16_moments_dtype():
    opt = adamw(moment_dtype=jnp.bfloat16)
    params = _quadratic_params()
    state = opt.init(params)
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree.leaves(state["mu"]))
    g = jax.grad(_loss)(params)
    _, state2 = opt.update(g, state, params, jnp.float32(0.1))
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree.leaves(state2["mu"]))


def test_adafactor_factored_shapes():
    opt = adafactor(min_dim_size_to_factor=4)
    params = dict(big=jnp.zeros((16, 8)), small=jnp.zeros((3,)))
    st = opt.init(params)
    assert st["v"]["big"]["r"].shape == (16,)
    assert st["v"]["big"]["c"].shape == (8,)
    assert st["v"]["small"]["v"].shape == (3,)


def test_grad_clip():
    opt = adamw(grad_clip=1.0, weight_decay=0.0)
    params = dict(w=jnp.zeros((4,)))
    st = opt.init(params)
    huge = dict(w=jnp.full((4,), 1e6))
    p2, _ = opt.update(huge, st, params, jnp.float32(1.0))
    # clipped update magnitude bounded by lr / (1-b1 corrections) ~ O(1)
    assert float(jnp.max(jnp.abs(p2["w"]))) < 10.0


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, 4096).astype(np.float32))
    q, s = _quantize(x)
    y = _dequantize(q, s, 4096)
    err = np.abs(np.asarray(x - y))
    blockmax = np.abs(np.asarray(x)).reshape(-1, 256).max(1)
    assert (err.reshape(-1, 256).max(1) <= blockmax / 127 + 1e-7).all()


def test_flatten_unflatten_grads():
    tree = dict(a=jnp.ones((3, 4), jnp.bfloat16),
                b=(jnp.zeros((5,), jnp.float32),))
    flat, meta = flatten_grads(tree)
    back = unflatten_grads(flat, meta)
    assert back["a"].dtype == jnp.bfloat16 and back["a"].shape == (3, 4)
    assert jax.tree.structure(back) == jax.tree.structure(tree)


def test_ef_psum_single_device_mesh():
    """Error feedback: the residual carries exactly what quantization lost,
    so the two-step sum is exact (single-device psum == identity)."""
    import functools
    from repro.optim.compress import ef_quantized_psum
    mesh = jax.make_mesh((1,), ("data",))
    fn = jax.jit(jax.shard_map(
        functools.partial(ef_quantized_psum, axes=("data",)),
        mesh=mesh, in_specs=(jax.P(), jax.P()),
        out_specs=(jax.P(), jax.P()),
        check_vma=False))
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(0, 1, 1024).astype(np.float32))
    err = jnp.zeros_like(g)
    r1, err = fn(g, err)
    r2, err = fn(g, err)
    total = np.asarray(r1 + r2)
    np.testing.assert_allclose(total, 2 * np.asarray(g), atol=2e-2)
    # with EF the *cumulative* error stays bounded by one quantization step
    assert float(jnp.max(jnp.abs(err))) < 0.05
