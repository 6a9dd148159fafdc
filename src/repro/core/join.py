"""Vector-join entry points (paper Alg. 1) — all methods of §5.1.2.

  nlj          exact nested-loop join (kernels/nlj.py)
  index        INLJ: per-query search from s_Y, no early stopping
  es           + early stopping (§4.1)
  es_hws       + hard work sharing  (= SIMJOIN [38], §4.2)
  es_sws       + soft work sharing  (§4.3)
  es_mi        merged index, greedy phase offloaded to construction (§4.4)
  es_mi_adapt  + adaptive hybrid BBFS for predicted-OOD queries (§4.5)

The wave runners live in ``repro.engine.waves``; the persistent serving
layer (index caching, streaming batches, sharded execution) is
``repro.engine.JoinEngine``. ``vector_join`` below is the one-shot
compatibility wrapper: it spins up a transient engine per call, so the
old build-per-invocation semantics are preserved exactly.

Every entry point takes the engine's metric (``core.types.METRICS``): θ
is a threshold on the space's dissimilarity, ‖x − y‖ or −⟨x, y⟩.

The NLJ has exactly one entry point, ``cascade_join_pairs``, driven by a
``repro.quant.FilterCascade``: with no cascade it is the exact
nested-loop ground truth; with tiers it filters every pair through the
certified-bounds chain and re-ranks only the ambiguous band in f32, so
the emitted set equals the exact one at every tier configuration.
``exact_join_pairs`` survives as the no-cascade alias. ``exact_ip_pairs``
and ``theta_band`` are the plain float64 references the tests hold every
path to.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.types import GraphIndex, JoinConfig, JoinResult, theta_key
from repro.kernels import ops


# ---------------------------------------------------------------------------
# the one NLJ entry point — FilterCascade-driven filter-then-rerank
# ---------------------------------------------------------------------------

def cascade_join_pairs(X, Y, theta: float, cascade=None, *,
                       block: int = 512, pair_block: int = 1 << 15,
                       impl: str | None = None, early_exit: bool = True,
                       metric: str = "l2") -> tuple[np.ndarray, dict]:
    """Exact NLJ through a ``FilterCascade``'s certified-bounds chain.

    Tier 0 streams its compressed codes pairwise against the whole of Y
    and brackets every pair with certified bounds: a lower bound ≥ θ²
    rejects (cannot lose a true pair); where the tier has upper bounds,
    an upper bound < θ² accepts (cannot admit a false one). Survivors
    escalate pair-by-pair through the remaining tiers (``pair_refine``,
    running maximum of lower bounds — the monotone chain), and only the
    final ambiguous band — pairs the confirming tier's bounds cannot
    resolve — is re-computed with exact f32 distances. The result equals
    the exact join for *any* tier subset, while f32 traffic stays
    proportional to the band.

    With ``cascade=None`` (or an empty cascade) this is the exact
    nested-loop ground truth. (Pairs within a few ulps of θ can differ
    between tier configurations: the no-cascade path evaluates the
    ill-conditioned matmul form while the re-rank uses the
    better-conditioned difference form — on such boundary pairs the
    cascade path agrees with float64.)

    An early-exitable tier 0 (``PdxTier``) runs its pairwise sweep
    against the threshold itself (``pairwise_bounds_ee``): with
    ``early_exit`` its kernel retires lanes mid-vector on the certified
    tail bound. Retirement implies the lane's certified lower bound
    exceeds θ², so the reject/sure/band partition — and therefore the
    emitted pairs and every count — is identical on/off; only
    ``counts["dims_scanned"]`` (dimensions actually scanned, vs
    ``counts["dims_total"]``) changes.

    Returns ``(pairs, counts)`` — the exact pair array plus per-tier
    survivor counts: ``counts["escalated"]`` has one entry per tier
    beyond the first (pairs that tier had to evaluate) and
    ``counts["n_rerank"]`` the f32 band evaluations.

    Under ``metric="ip"`` only the no-cascade path exists (the tiers'
    bounds are L2 bounds): the exact pairs are those with −⟨x, y⟩ < θ
    by ``ops.pairwise_neg_ip``.
    """
    X = jnp.asarray(X, jnp.float32)
    Y = jnp.asarray(Y, jnp.float32)
    tiers = tuple(cascade.tiers) if cascade is not None else ()
    if tiers and metric != "l2":
        raise ValueError(f"the filter cascade certifies L2 bounds; metric "
                         f"{metric!r} joins with quant 'off'")
    th2 = np.float32(theta_key(theta, metric))
    counts = {"escalated": [0] * max(len(tiers) - 1, 0), "n_rerank": 0,
              "dims_scanned": 0, "dims_total": 0}

    if not tiers:
        counts["escalated"] = ()
        out = []
        for q0 in range(0, X.shape[0], block):
            q1 = min(q0 + block, X.shape[0])
            mask = np.asarray(ops.nlj_mask(X[q0:q1], Y, theta=float(theta),
                                           impl=impl, metric=metric))
            qi, yi = np.nonzero(mask)
            out.append(np.stack([qi + q0, yi], axis=1))
        pairs = (np.concatenate(out, axis=0) if out
                 else np.empty((0, 2), np.int64)).astype(np.int64)
        return pairs, counts

    out: list[np.ndarray] = []
    for q0 in range(0, X.shape[0], block):
        q1 = min(q0 + block, X.shape[0])
        xb = X[q0:q1]
        qc0 = tiers[0].encode(xb)
        if getattr(tiers[0], "early_exitable", False):
            lb, ub, nscan = tiers[0].pairwise_bounds_ee(
                qc0, theta=jnp.float32(theta), early_exit=early_exit,
                impl=impl)
            st0 = tiers[0].store
            dims = np.minimum(np.asarray(nscan) * st0.slab, st0.dim)
            counts["dims_scanned"] += int(dims.sum())
            counts["dims_total"] += int(dims.size) * st0.dim
        else:
            lb, ub = tiers[0].pairwise_bounds(qc0, impl=impl)
        lb = np.asarray(lb)
        if ub is not None and len(tiers) == 1:
            # single tier with upper bounds: emit certified-sure pairs
            # straight from the pairwise sweep (the sq8 fast path)
            sure = np.asarray(ub) < th2
            qi, yi = np.nonzero(sure)
            out.append(np.stack([qi + q0, yi], axis=1))
            qi, yi = np.nonzero((lb < th2) & ~sure)
        else:
            qi, yi = np.nonzero(lb < th2)
        if not qi.size:
            continue
        if len(tiers) == 1:
            counts["n_rerank"] += int(qi.size)
            out.append(_rerank_pairs(xb, Y, qi, yi, q0, th2))
            continue
        # escalate survivors through the remaining tiers, pair-blocked;
        # queries are encoded per tier once per block, lazily (a block
        # whose tier-0 sweep prunes everything encodes nothing else)
        qcs = [tiers[i].encode(xb) for i in range(1, len(tiers))]
        for p0 in range(0, qi.size, pair_block):
            qp, yp = qi[p0:p0 + pair_block], yi[p0:p0 + pair_block]
            plb = lb[qp, yp]
            pub = None
            keep = np.ones(qp.size, bool)
            for t, tier in enumerate(tiers[1:]):
                counts["escalated"][t] += int(keep.sum())
                # collapse already-rejected pairs to index 0 — their
                # bounds are computed but ignored (fixed host shapes)
                tq = np.where(keep, qp, 0)
                ty = np.where(keep, yp, 0)
                tlb, tub = tier.pair_refine(qcs[t], tq, ty)
                plb = np.where(keep, np.maximum(plb, np.asarray(tlb)), plb)
                if tub is not None:
                    pub = np.where(keep, np.asarray(tub), np.inf)
                keep = keep & (plb < th2)
            if pub is not None:
                sure = keep & (pub < th2)
                psel = np.flatnonzero(sure)
                out.append(np.stack([qp[psel] + q0, yp[psel]], axis=1))
                amb = keep & ~sure
            else:
                amb = keep
            counts["n_rerank"] += int(amb.sum())
            if amb.any():
                asel = np.flatnonzero(amb)
                out.append(_rerank_pairs(xb, Y, qp[asel], yp[asel], q0,
                                         th2))
    pairs = (np.concatenate(out, axis=0) if out
             else np.empty((0, 2), np.int64)).astype(np.int64)
    counts["escalated"] = tuple(counts["escalated"])
    return pairs, counts


def _rerank_pairs(xb, Y, qi, yi, q0: int, th2) -> np.ndarray:
    """Exact f32 difference-form distances for explicit band pairs."""
    diff = xb[jnp.asarray(qi)] - Y[jnp.asarray(yi)]
    d = np.asarray(jnp.sum(diff * diff, axis=1))
    m = d < th2
    return np.stack([qi + q0, yi], axis=1)[m]


def exact_join_pairs(X, Y, theta: float, *, block: int = 1024,
                     impl: str | None = None, metric: str = "l2"
                     ) -> np.ndarray:
    """All (query, data) pairs whose dissimilarity is < theta — the ground
    truth (the no-cascade configuration of ``cascade_join_pairs``)."""
    pairs, _ = cascade_join_pairs(X, Y, theta, None, block=block, impl=impl,
                                  metric=metric)
    return pairs


# Relative width of the inner product's rounding band at θ: 8 float32 ulps
# (2⁻²³ each) of ‖x‖·‖y‖, which bounds Σ|xᵢyᵢ| (Cauchy–Schwarz), the
# magnitude every rounding of a float32 dot is relative to. The program's
# dots (the row-wise kernel's blocked product sums, the matmul kernels at
# ``Precision.HIGHEST``) sum d ≤ 1024 terms in a tree of partial sums, a
# few roundings deep, well inside it; bfloat16 inputs (2⁻⁸) are not.
DOT_GUARD = 8 * 2.0 ** -23


def exact_ip_pairs(X, Y, theta: float) -> np.ndarray:
    """The plain inner-product reference: every (query, data) pair with
    −⟨x, y⟩ < θ in float64 (host numpy; no kernel, cache or batching —
    small inputs only)."""
    x = np.asarray(X, np.float64)
    y = np.asarray(Y, np.float64)
    qi, yi = np.nonzero(-(x @ y.T) < theta)
    return np.stack([qi, yi], axis=1).astype(np.int64)


def theta_band(X, Y, theta: float, metric: str = "l2") -> set:
    """Pairs whose float64 dissimilarity lies within its f32 rounding
    band at θ (host numpy, small inputs only): for ``l2`` the squared
    distance within the matmul rounding guard of θ²; for ``ip``, −⟨x, y⟩
    within ``DOT_GUARD``·‖x‖·‖y‖ of θ.

    On these pairs two correct f32 evaluations may disagree — the
    matmul form of the exact NLJ against the difference form of the
    re-rank, or two partitionings of one reduction — so tests compare
    pair sets up to this band."""
    x = np.asarray(X, np.float64)
    y = np.asarray(Y, np.float64)
    if metric == "ip":
        band = DOT_GUARD * np.outer(np.linalg.norm(x, axis=1),
                                    np.linalg.norm(y, axis=1))
        qi, yi = np.nonzero(np.abs(-(x @ y.T) - theta) <= band)
        return set(zip(qi.tolist(), yi.tolist()))
    from repro.quant.cascade import MATMUL_GUARD
    d2 = np.sum((x[:, None, :] - y[None, :, :]) ** 2, axis=-1)
    norms = np.sum(x * x, axis=1)[:, None] + np.sum(y * y, axis=1)[None, :]
    qi, yi = np.nonzero(np.abs(d2 - theta * theta) <= MATMUL_GUARD * norms)
    return set(zip(qi.tolist(), yi.tolist()))


# ---------------------------------------------------------------------------
# one-shot compatibility wrapper over the engine
# ---------------------------------------------------------------------------

def vector_join(X, Y, cfg: JoinConfig, *,
                index_y: GraphIndex | None = None,
                index_x: GraphIndex | None = None,
                index_merged: GraphIndex | None = None,
                build_kw: dict | None = None) -> JoinResult:
    """Run the configured join method. Indexes are built if not supplied
    (offline phase; supply prebuilt ones — or hold a
    ``repro.engine.JoinEngine`` — to amortize across thresholds)."""
    from repro.engine import JoinEngine  # local import to avoid cycles

    eng = JoinEngine(Y, build_kw=build_kw, default=cfg)
    return eng.join(X, cfg, index_y=index_y, index_x=index_x,
                    index_merged=index_merged)
