"""SketchStore — 1-bit binary sketches with certified L2 lower bounds.

The progressive-refinement tier *above* QuantStore (PDX-style cascade:
prune with 1-bit sketches, confirm with int8, re-rank the band in f32).
Each vector is reduced to the **sign bits of its rotated, centered
coordinates**, packed into uint32 lanes — d/32 words ≈ 32× less data than
f32 — plus an exact per-vector *sketch-error slack table* that turns
Hamming distances between codes into certified lower bounds on true L2
distances:

  * ``codes`` — bit i of a row is ``z_i > 0`` where ``z = R (v − μ)``;
    ``R`` is a seeded random rotation (QR of a Gaussian matrix) that
    equidistributes each vector's energy across coordinates, and ``μ``
    the data mean. Bits are packed little-endian into ⌈d/32⌉ uint32s.
  * ``cum``   — per-vector order-statistics checkpoints: ``cum[k]`` is
    the **exact** sum of the ``hs[k]`` smallest squared rotated
    coordinates (``hs[0] = 0 … hs[-1] = d``, so ``cum[-1] = ‖z‖²``).
    Computed at build/encode time per row — a slack table, not a bound.
  * ``iso``   — certified isometry factor for the *actual f32* rotation
    matrix: ``R`` is orthonormal only up to float rounding, so distances
    in the rotated domain relate to original distances through its true
    singular values, computed once in float64 at build time.

Hamming → L2 derivation (docs/ARCHITECTURE.md §3 carries the prose): let
``D`` be the set of dimensions where the sign bits of ``zx`` and ``zy``
differ, ``h = |D|`` their Hamming distance. Signs differing means
``zx_i · zy_i ≤ 0``, hence ``(zx_i − zy_i)² ≥ zx_i² + zy_i²`` exactly, so

    ‖zx − zy‖²  ≥  Σ_{i∈D} zx_i² + zy_i²  ≥  cum_x(h) + cum_y(h)   (lb₁)

by order statistics (any h coordinates dominate the h smallest). And with
``n = ‖z‖²``, Cauchy–Schwarz over the *agreeing* dimensions bounds the
inner product: ``⟨zx, zy⟩ ≤ √((n_x − cum_x(h)) (n_y − cum_y(h)))``, so

    ‖zx − zy‖²  ≥  n_x + n_y − 2 √((n_x − cum_x(h)) (n_y − cum_y(h)))  (lb₂)

``sketch_lower_bound`` takes ``max(lb₁, lb₂)``, scales by ``iso`` and
subtracts a small rounding guard — a certified lower bound on
``‖x − y‖²``: a threshold test on it never rejects a true pair, so the
sketch tier can only *prune* work, exactly like the sq8 tier's bounds.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

WORD_BITS = 32
# Checkpoint grid: k/16 of d for k = 0..15, plus d itself. Finer tables
# buy little (the bound's looseness is dominated by Cauchy–Schwarz, not
# checkpoint flooring) and each checkpoint is 4 bytes/vector.
DEFAULT_N_CHECKPOINTS = 16

# Certification guards for f32 arithmetic. The rotation matmul and the
# cum prefix sums accumulate d terms, so their worst-case rounding grows
# with dimension (~d·eps·‖z‖² absolute for a sequential sum; random data
# is ~√d·eps). The guard therefore carries a d-scaled term on top of a
# fixed floor: ``(_GUARD + _GUARD_PER_DIM·d)·(n_x + n_y)`` stays an
# order of magnitude above worst case at any supported d (≈ 1e-3 of the
# norms at d = 2048) while costing a vanishing amount of pruning power.
_ISO_SLACK = 1e-4
_GUARD = 1e-4
_GUARD_PER_DIM = 4 * 1.2e-7


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SketchStore:
    """1-bit companion of a vector table (or ``GraphIndex.vecs``)."""
    codes: Array            # (N, W) uint32 packed sign bits, W = ⌈d/32⌉
    cum: Array              # (N, K) f32 exact order-statistics slack table
    hs: Array               # (K,) int32 checkpoint Hamming values (0 … d)
    mu: Array               # (d,) f32 center
    rot: Array              # (d, d) f32 rotation R (z = R (v − μ))
    iso: Array              # () f32 certified isometry factor (≤ 1)

    @property
    def n_vectors(self) -> int:
        return self.codes.shape[0]

    @property
    def dim(self) -> int:
        return self.mu.shape[0]

    @property
    def n_words(self) -> int:
        return self.codes.shape[1]

    @property
    def n_checkpoints(self) -> int:
        return self.hs.shape[0]

    @property
    def nbytes(self) -> int:
        """Bytes resident for the sketch artifact (the rotation is the
        only O(d²) term; codes + cum dominate for real N)."""
        from repro.quant.store import arrays_nbytes
        return arrays_nbytes(self.codes, self.cum, self.hs, self.mu,
                             self.rot, self.iso)


def checkpoint_grid(d: int, n_checkpoints: int = DEFAULT_N_CHECKPOINTS
                    ) -> np.ndarray:
    """Monotone Hamming checkpoints ``0 = hs[0] < … ≤ hs[-1] = d``."""
    ks = (np.arange(n_checkpoints) * d) // n_checkpoints
    return np.unique(np.concatenate([ks, [d]])).astype(np.int32)


def _pack_bits(bits: Array) -> Array:
    """(N, d) bool → (N, ⌈d/32⌉) uint32, little-endian within each word.
    Padding bits are 0 for every vector, so they never differ."""
    n, d = bits.shape
    W = -(-max(d, 1) // WORD_BITS)
    pad = W * WORD_BITS - d
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((n, pad), bits.dtype)], axis=1)
    w = bits.reshape(n, W, WORD_BITS).astype(jnp.uint32)
    shift = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(w << shift, axis=-1, dtype=jnp.uint32)


@jax.jit
def sketch_encode(x: Array, mu: Array, rot: Array, hs: Array
                  ) -> tuple[Array, Array]:
    """Encode rows on an existing sketch grid → ``(codes, cum)``.

    The single definition of the code scheme — store build, query
    encoding, and the sharded in-shard path all route through it, so the
    certified bounds can never diverge between producers (mirrors
    ``store.quantize_on_grid``).
    """
    x = jnp.asarray(x, jnp.float32)
    z = jnp.matmul(x - mu, rot.T, precision=jax.lax.Precision.HIGHEST)
    codes = _pack_bits(z > 0)
    s = jnp.sort(z * z, axis=1)
    cumfull = jnp.concatenate(
        [jnp.zeros((x.shape[0], 1), jnp.float32), jnp.cumsum(s, axis=1)],
        axis=1)
    return codes, cumfull[:, hs]


@functools.lru_cache(maxsize=8)
def make_rotation(d: int, seed: int = 0) -> tuple[np.ndarray, np.float32]:
    """Seeded random rotation + its certified isometry factor.

    The factor certifies the *actual f32* matrix:
    ``‖x − y‖² ≥ ‖R (x − y)‖² / σ_max²`` with σ_max computed in float64.
    Depends only on (d, seed), so the O(d³) QR + SVD is memoized:
    repeated store builds (per shard, per streaming batch) share one
    rotation. Callers must treat the returned array as read-only.
    """
    rng = np.random.default_rng(seed)
    R = np.linalg.qr(rng.normal(size=(d, d)))[0].astype(np.float32)
    sigma_max = float(np.linalg.svd(R.astype(np.float64),
                                    compute_uv=False).max())
    return R, np.float32((1.0 - _ISO_SLACK) / sigma_max ** 2)


def build_sketch(vecs, *, n_checkpoints: int = DEFAULT_N_CHECKPOINTS,
                 seed: int = 0, scale_rows=None,
                 rotation: tuple[np.ndarray, np.float32] | None = None
                 ) -> SketchStore:
    """Sketch a vector table once (index-build time, offline phase).

    ``scale_rows`` optionally masks which rows contribute to the center
    ``μ`` (all by default). Rows outside the mask are still encoded —
    their ``cum`` table is exact per row, so their bounds stay certified;
    far-away sentinel pad rows get a *huge* slack table and are pruned by
    their own bound (used by the sharded path). ``rotation`` optionally
    supplies a precomputed ``make_rotation(d, seed)`` pair so repeated
    builds (one per shard) skip the O(d³) QR + SVD.
    """
    v = np.asarray(vecs, np.float32)
    _, d = v.shape
    R, iso = rotation if rotation is not None else make_rotation(d, seed)
    src = v
    if scale_rows is not None:
        scale_rows = np.asarray(scale_rows, bool)
        if scale_rows.any():
            src = v[scale_rows]
    mu = src.mean(axis=0).astype(np.float32)
    hs = checkpoint_grid(d, n_checkpoints)
    codes, cum = sketch_encode(jnp.asarray(v), jnp.asarray(mu),
                               jnp.asarray(R), jnp.asarray(hs))
    return SketchStore(codes=codes, cum=cum, hs=jnp.asarray(hs),
                       mu=jnp.asarray(mu), rot=jnp.asarray(R),
                       iso=jnp.asarray(iso))


def sketch_queries(x, store: SketchStore) -> tuple[Array, Array]:
    """Encode queries on the store's grid → ``(codes, cum)``."""
    return sketch_encode(jnp.asarray(x, jnp.float32), store.mu, store.rot,
                         store.hs)


def _lb_from_cum(cq: Array, cc: Array, nq: Array, nc: Array,
                 iso, d) -> Array:
    """Core bound: ``max(lb₁, lb₂)`` with isometry + rounding guards.
    ``cq``/``cc`` are the checkpointed slack values at the pair's Hamming
    distance; ``nq``/``nc`` the full squared norms (the last checkpoint);
    ``d`` the true dimension (scales the rounding guard — see module
    header).
    """
    lb1 = cq + cc
    lb2 = nq + nc - 2.0 * jnp.sqrt(jnp.maximum(nq - cq, 0.0)
                                   * jnp.maximum(nc - cc, 0.0))
    lb = jnp.maximum(jnp.maximum(lb1, lb2), 0.0)
    guard = (jnp.float32(_GUARD)
             + jnp.float32(_GUARD_PER_DIM) * d.astype(jnp.float32))
    return jnp.maximum(iso * lb - guard * (nq + nc), 0.0)


def _checkpoint_index(h: Array, hs: Array) -> Array:
    """Largest k with ``hs[k] ≤ h`` (hs[0] = 0 ⇒ always ≥ 0)."""
    return jnp.sum(h[..., None] >= hs, axis=-1).astype(jnp.int32) - 1


def sketch_lower_bound_pairwise(h: Array, cum_q: Array, cum_c: Array,
                                hs: Array, iso) -> Array:
    """(B, N) Hamming counts → (B, N) certified lower bounds on ‖x−y‖².

    ``cum_q`` (B, K) are the query slack tables, ``cum_c`` (N, K) the
    store's."""
    kidx = _checkpoint_index(h, hs)                        # (B, N)
    cq = jnp.take_along_axis(cum_q, kidx, axis=1)          # (B, N)
    n = cum_c.shape[0]
    cc = cum_c[jnp.arange(n)[None, :], kidx]               # (B, N)
    return _lb_from_cum(cq, cc, cum_q[:, -1:], cum_c[None, :, -1],
                        iso, hs[-1])


def sketch_lower_bound_rowwise(h: Array, cum_q: Array, cum_cands: Array,
                               hs: Array, iso) -> Array:
    """(B, K) Hamming counts over gathered candidates → certified lower
    bounds. ``cum_cands`` (B, K, Kc) are candidate slack tables gathered
    by the caller (tests and small-batch callers; the traversal hot path
    uses ``sketch_lower_bound_gather`` to avoid materializing them)."""
    kidx = _checkpoint_index(h, hs)                        # (B, K)
    cq = jnp.take_along_axis(cum_q, kidx, axis=1)          # (B, K)
    cc = jnp.take_along_axis(cum_cands, kidx[..., None], axis=2)[..., 0]
    return _lb_from_cum(cq, cc, cum_q[:, -1:], cum_cands[..., -1],
                        iso, hs[-1])


def sketch_lower_bound_gather(h: Array, cum_q: Array, cum_table: Array,
                              cand: Array, hs: Array, iso
                              ) -> tuple[Array, Array]:
    """(B, K) Hamming counts + candidate ids → certified lower bounds,
    gathering only the two needed slack entries per candidate (8 bytes:
    the checkpoint at ``h`` and the norm) from the store's (N, Kc) table
    — the traversal hot path's form, keeping the sketch tier's gather
    traffic at d/8 + 8 bytes per candidate.

    Returns ``(lb, norms)`` — the candidate norms ride along for the
    caller's navigation estimate (they were gathered anyway)."""
    kidx = _checkpoint_index(h, hs)                        # (B, K)
    cq = jnp.take_along_axis(cum_q, kidx, axis=1)          # (B, K)
    cc = cum_table[cand, kidx]                             # (B, K)
    nc = cum_table[cand, -1]                               # (B, K)
    return _lb_from_cum(cq, cc, cum_q[:, -1:], nc, iso, hs[-1]), nc


def sketch_survivors(x, store: SketchStore, theta: float) -> np.ndarray:
    """(B, N) bool — which store rows the sketch tier *cannot* certify
    out of θ-range for each query row: ``lb(x_b, y_n) ≤ θ²``.

    The LSH selectivity primitive behind ``plan.LshEstimator``: the
    survivor mask over a sampled store is a certified **superset** of
    the true in-range mask (the lower bounds never reject a true pair),
    so per-query survivor counts upper-bound band occupancy and their
    scaled sum upper-bounds join size on the sample. All shapes are
    fixed by (B, N, d), so repeated calls on a cached sample reuse the
    jit specializations of ``sketch_encode`` and the bound kernel.
    """
    qcodes, qcum = sketch_queries(np.asarray(x, np.float32), store)
    from repro.kernels import ops
    h = ops.pairwise_hamming(qcodes, store.codes)
    lb = sketch_lower_bound_pairwise(h, qcum, store.cum, store.hs,
                                     store.iso)
    return np.asarray(lb <= np.float32(theta) ** 2)
