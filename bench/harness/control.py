"""The control: the plain reference put in the program's place, computed
one precision below what the configuration states — bfloat16 vectors for
a float32 deployment, the step that would tempt a change that halves the
bytes per candidate row. The benchmark's comparison has to call its
answers not correct; ``tools/control.py`` runs it at a cell's own size and
``tests/bench`` keeps it at a small one."""
from __future__ import annotations

import numpy as np

PRECISIONS = {"float32": "bfloat16"}


def control_pairs(X: np.ndarray, Y: np.ndarray, theta: float, *,
                  precision: str = "float32", block: int = 512
                  ) -> np.ndarray:
    """Every (query, row) pair closer than θ, by the matmul form on
    vectors rounded to the precision below ``precision`` and sums in
    float32, on the default device."""
    import jax
    import jax.numpy as jnp

    low = jnp.dtype(PRECISIONS[precision])
    Yl = jnp.asarray(Y).astype(low)
    yn = jnp.sum(jnp.square(Yl.astype(jnp.float32)), axis=1)
    th2 = jnp.float32(theta) ** 2

    @jax.jit
    def packed_mask(xb, Yl, yn):
        xl = xb.astype(low)
        xn = jnp.sum(jnp.square(xl.astype(jnp.float32)), axis=1)
        dot = jnp.matmul(xl, Yl.T, preferred_element_type=jnp.float32)
        return jnp.packbits(xn[:, None] + yn[None, :] - 2.0 * dot < th2,
                           axis=1)

    out = []
    n = Y.shape[0]
    for q0 in range(0, X.shape[0], block):
        xb = np.zeros((block, X.shape[1]), np.float32)
        rows = X[q0:q0 + block]
        xb[:len(rows)] = rows
        bits = np.unpackbits(np.asarray(packed_mask(xb, Yl, yn)),
                             axis=1)[:, :n]
        qi, yi = np.nonzero(bits[:len(rows)])
        out.append(np.stack([qi + q0, yi], axis=1))
    return np.concatenate(out).astype(np.int64) if out else \
        np.empty((0, 2), np.int64)
