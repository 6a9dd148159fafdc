"""Distributed join correctness — runs in a subprocess so it can force 8
host devices without contaminating the rest of the suite (which must see
one device)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.data.vectors import make_dataset, thresholds
    from repro.core import exact_join_pairs, TraversalConfig
    from repro.core.distributed import (build_sharded_merged_index,
                                        distributed_mi_join,
                                        make_distributed_nlj_count)

    ds = make_dataset("manifold", n_data=2000, n_query=96, dim=24, seed=5)
    theta = float(thresholds(ds, 3)[1])
    truth = set(map(tuple, exact_join_pairs(ds.X, ds.Y, theta).tolist()))
    assert len(truth) > 0

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    smi = build_sharded_merged_index(ds.Y, ds.X, 4, k=32, degree=16)
    tc = TraversalConfig(beam_width=64, expand_per_iter=4, pool_cap=512,
                         hybrid_beam=64, seeds_max=8, max_iters=1024)
    pairs, st = distributed_mi_join(ds.X, smi, mesh, ("pod", "data"),
                                    theta=theta, cfg=tc, wave_size=48)
    found = set(map(tuple, pairs.tolist()))
    # soundness across shards
    for q, y in found:
        assert np.linalg.norm(ds.X[q] - ds.Y[y]) < theta
    rec = len(found & truth) / len(truth)
    assert rec >= 0.8, rec

    # 2-D sharded exact NLJ == brute force
    nlj = make_distributed_nlj_count(mesh, ("pod", "data"), "model",
                                     theta=theta)
    with jax.set_mesh(mesh):
        cnt = np.asarray(nlj(jnp.asarray(ds.X[:32]), jnp.asarray(ds.Y)))
    ref = np.array([(np.linalg.norm(ds.X[i] - ds.Y, axis=1) < theta).sum()
                    for i in range(32)])
    assert (cnt == ref).all()
    print("DISTRIBUTED_OK recall=%.3f" % rec)
""")


@pytest.mark.slow
def test_distributed_join_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DISTRIBUTED_OK" in r.stdout
