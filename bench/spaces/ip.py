"""Inner product: θ bounds the dissimilarity −⟨x, y⟩, so a pair joins when
−⟨x, y⟩ < θ, that is ⟨x, y⟩ > −θ.

Written for the benchmark alone; it shares no code with the join.
``GUARD`` is the relative width of the rounding band at θ: a pair whose
float64 −⟨x, y⟩ lies within ``GUARD·‖x‖·‖y‖`` of θ is a tie that any
float32 evaluation may round either way. It is 8 float32 ulps (2⁻²³ each)
of ‖x‖·‖y‖, which bounds Σ|xᵢyᵢ|, the magnitude every rounding of a
float32 dot is relative to; a float32 dot of a few hundred terms summed
in a tree rounds well inside it, a bfloat16 one (2⁻⁸ per input) does
not."""
import numpy as np

GUARD = 8 * 2.0 ** -23


def within(xs: np.ndarray, ys: np.ndarray, theta: float) -> np.ndarray:
    """(len(xs), len(ys)) mask of the pairs that join, for float64
    blocks."""
    return -(xs @ ys.T) < theta


def band(xs: np.ndarray, ys: np.ndarray) -> np.ndarray:
    """Width of the rounding band at θ of each matched float64 pair."""
    return GUARD * np.linalg.norm(xs, axis=1) * np.linalg.norm(ys, axis=1)


def beyond(xs: np.ndarray, ys: np.ndarray, theta: float) -> np.ndarray:
    """For matched float64 rows, the pairs whose −⟨x, y⟩ exceeds θ beyond
    the band."""
    return -np.sum(xs * ys, axis=1) >= theta + band(xs, ys)


def distance(xs: np.ndarray, ys: np.ndarray) -> np.ndarray:
    """−⟨x, y⟩ of each matched pair, in the rows' own precision."""
    return -np.sum(xs * ys, axis=1)


def low_table(Yl):
    """The table in the lower precision (a dot needs no norms)."""
    return Yl


def low_within(xl, table, theta: float):
    """The control's mask (traced under ``jax.jit``): lower-precision
    vectors, their products summed in float32."""
    import jax.numpy as jnp
    dot = jnp.matmul(xl, table.T, preferred_element_type=jnp.float32)
    return -dot < jnp.float32(theta)
