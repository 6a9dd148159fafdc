"""Euclidean distance: a pair joins when ‖x − y‖ < θ.

The float64 tests are copied from ``chip_smoke.py`` at commit cb1b0a7 and
share no code with the join. ``GUARD`` is the relative width of the
rounding band at θ: a pair whose float64 squared distance lies within
``GUARD·(|x|² + |y|²)`` of θ² is a tie that any float32 evaluation may round
either way (eight float32 ulps of the matmul form's norms, as
``quant.cascade.MATMUL_GUARD`` states it in the program; restated here so
the yardstick does not follow the program)."""
import numpy as np

GUARD = 8 * 1.2e-7


def within(xs: np.ndarray, ys: np.ndarray, theta: float) -> np.ndarray:
    """(len(xs), len(ys)) mask of the pairs closer than θ, for float64
    blocks, by the matmul form."""
    xn = np.sum(xs * xs, axis=1)
    yn = np.sum(ys * ys, axis=1)
    d2 = xn[:, None] + yn[None, :] - 2.0 * (xs @ ys.T)
    return d2 < theta * theta


def band(xs: np.ndarray, ys: np.ndarray) -> np.ndarray:
    """Width of the rounding band at θ² of each matched float64 pair."""
    return GUARD * (np.sum(xs * xs, axis=1) + np.sum(ys * ys, axis=1))


def beyond(xs: np.ndarray, ys: np.ndarray, theta: float) -> np.ndarray:
    """For matched float64 rows, the pairs whose distance exceeds θ
    beyond the band."""
    d2 = np.sum((xs - ys) ** 2, axis=1)
    return d2 >= theta * theta + band(xs, ys)


def distance(xs: np.ndarray, ys: np.ndarray) -> np.ndarray:
    """The distance of each matched pair, in the rows' own precision."""
    return np.linalg.norm(xs - ys, axis=1)


def low_table(Yl):
    """What every block of the control's evaluation reuses: the table in
    the lower precision and its squared norms summed in float32."""
    import jax.numpy as jnp
    return Yl, jnp.sum(jnp.square(Yl.astype(jnp.float32)), axis=1)


def low_within(xl, table, theta: float):
    """The control's mask (traced under ``jax.jit``): the matmul form on
    lower-precision vectors with float32 sums."""
    import jax.numpy as jnp
    Yl, yn = table
    xn = jnp.sum(jnp.square(xl.astype(jnp.float32)), axis=1)
    dot = jnp.matmul(xl, Yl.T, preferred_element_type=jnp.float32)
    return xn[:, None] + yn[None, :] - 2.0 * dot < jnp.float32(theta) ** 2
