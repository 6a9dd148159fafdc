"""The control: the plain reference put in the program's place, computed
one precision below what the configuration states — bfloat16 vectors for
a float32 deployment, the step that would tempt a change that halves the
bytes per candidate row. The evaluation is the configuration's space's
(``low_table`` and ``low_within`` of ``bench/spaces/<metric>.py``). The
benchmark's comparison has to call its answers not correct;
``tools/control.py`` runs it at a cell's own size and ``tests/bench`` keeps
it at a small one."""
from __future__ import annotations

import numpy as np

PRECISIONS = {"float32": "bfloat16"}


def control_pairs(X: np.ndarray, Y: np.ndarray, theta: float, space, *,
                  precision: str = "float32", block: int = 512
                  ) -> np.ndarray:
    """Every (query, row) pair that joins by the space's evaluation on
    vectors rounded to the precision below ``precision``, on the default
    device."""
    import jax
    import jax.numpy as jnp

    low = jnp.dtype(PRECISIONS[precision])
    table = space.low_table(jnp.asarray(Y).astype(low))

    @jax.jit
    def packed_mask(xb, table):
        return jnp.packbits(space.low_within(xb.astype(low), table, theta),
                            axis=1)

    out = []
    n = Y.shape[0]
    for q0 in range(0, X.shape[0], block):
        xb = np.zeros((block, X.shape[1]), np.float32)
        rows = X[q0:q0 + block]
        xb[:len(rows)] = rows
        bits = np.unpackbits(np.asarray(packed_mask(xb, table)),
                             axis=1)[:, :n]
        qi, yi = np.nonzero(bits[:len(rows)])
        out.append(np.stack([qi + q0, yi], axis=1))
    return np.concatenate(out).astype(np.int64) if out else \
        np.empty((0, 2), np.int64)
