"""Checkpoint manager: atomic commit, async save, bf16 round-trip, GC,
elastic restore, heartbeat."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_tree, save_tree


def _tree(seed=0):
    k = jax.random.key(seed)
    return dict(
        w=jax.random.normal(k, (8, 16), jnp.float32),
        b=jax.random.normal(k, (4,), jnp.bfloat16),
        layers=(dict(q=jnp.arange(12, dtype=jnp.int32).reshape(3, 4)),),
        step=jnp.int32(7),
    )


def test_roundtrip_including_bf16(tmp_path):
    t = _tree()
    save_tree(t, str(tmp_path / "ck"))
    back = restore_tree(str(tmp_path / "ck"), t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_manager_save_restore_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree()
    mgr.save(10, t)
    mgr.save(20, t)           # waits for the previous save internally
    mgr.wait()
    assert mgr.steps() == [10, 20]
    step, back = mgr.restore(t)
    assert step == 20
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(t["w"]))


def test_gc_keeps_last_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, t, blocking=True)
    assert mgr.steps() == [3, 4]


def test_crash_mid_save_never_corrupts(tmp_path):
    """A stray .tmp dir (simulated crash) is invisible to restore and
    cleaned by the next save."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    t = _tree()
    mgr.save(5, t, blocking=True)
    os.makedirs(str(tmp_path / "step_0000000009.tmp"))
    assert mgr.latest_step() == 5
    step, _ = mgr.restore(t)
    assert step == 5
    mgr.save(6, t, blocking=True)
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


def test_restore_missing_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        mgr.restore(_tree())


def test_heartbeat(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.heartbeat(42, loss=1.5)
    hb = mgr.read_heartbeat()
    assert hb["step"] == 42 and hb["loss"] == 1.5


def test_elastic_restore_with_shardings(tmp_path):
    """Restore onto explicit (single-device here; any mesh in prod)
    shardings — the elastic-scaling path."""
    t = _tree()
    save_tree(t, str(tmp_path / "ck"))
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(mesh, jax.P()), t)
    back = restore_tree(str(tmp_path / "ck"), t, shardings=sh)
    assert all(l.sharding == jax.sharding.NamedSharding(mesh, jax.P())
               for l in jax.tree.leaves(back))
