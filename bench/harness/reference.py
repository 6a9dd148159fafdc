"""The plain reference: exact float64 threshold join on the host.

``reference`` and ``beyond_theta`` are copied from ``chip_smoke.py`` at
commit cb1b0a7; they share no code with the join. ``GUARD`` is the
relative width of the rounding band at θ: a pair whose float64 squared
distance lies within ``GUARD·(|x|² + |y|²)`` of θ² is a tie that any
float32 evaluation may round either way (eight float32 ulps of the
matmul form's norms, as ``quant.cascade.MATMUL_GUARD`` states it in the
program; restated here so the yardstick does not follow the program).
"""
from __future__ import annotations

import dataclasses

import numpy as np

GUARD = 8 * 1.2e-7


@dataclasses.dataclass
class Reference:
    """Exact pairs of the sample queries. Query ids are positions in the
    sample (0 .. len(sample) - 1)."""
    theta: float
    truth: set          # (q, y) with float64 distance < θ


def reference(Xs: np.ndarray, Y: np.ndarray, theta: float,
              block: int = 32768) -> Reference:
    """Exact join of the sample queries ``Xs`` against all of ``Y``."""
    xs = Xs.astype(np.float64)
    xn = np.sum(xs * xs, axis=1)
    th2 = theta * theta
    truth = set()
    for y0 in range(0, Y.shape[0], block):
        yb = Y[y0:y0 + block].astype(np.float64)
        yn = np.sum(yb * yb, axis=1)
        d2 = xn[:, None] + yn[None, :] - 2.0 * (xs @ yb.T)
        qi, yi = np.nonzero(d2 < th2)
        truth.update(zip(qi.tolist(), (yi + y0).tolist()))
    return Reference(theta, truth)


def beyond_theta(pairs: np.ndarray, X: np.ndarray, Y: np.ndarray,
                 theta: float, block: int = 1 << 17) -> int:
    """Emitted pairs whose float64 distance exceeds θ beyond the band,
    or whose ids lie outside ``X``/``Y`` (an answer that names no row is
    as wrong as one beyond θ)."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    ok = ((pairs[:, 0] >= 0) & (pairs[:, 0] < X.shape[0])
          & (pairs[:, 1] >= 0) & (pairs[:, 1] < Y.shape[0]))
    bad = int(np.count_nonzero(~ok))
    pairs = pairs[ok]
    for p0 in range(0, len(pairs), block):
        q, y = pairs[p0:p0 + block, 0], pairs[p0:p0 + block, 1]
        xq, yy = X[q].astype(np.float64), Y[y].astype(np.float64)
        d2 = np.sum((xq - yy) ** 2, axis=1)
        tol = GUARD * (np.sum(xq * xq, axis=1) + np.sum(yy * yy, axis=1))
        bad += int(np.count_nonzero(d2 >= theta * theta + tol))
    return bad


def duplicates(pairs: np.ndarray) -> int:
    """Pairs emitted more than once."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    return len(pairs) - len(np.unique(pairs, axis=0))


@dataclasses.dataclass
class Tally:
    """What the timed path's answers say against the reference."""
    found: int = 0         # reference pairs the program emitted
    wanted: int = 0        # reference pairs of the checked queries
    beyond: int = 0        # emitted pairs beyond θ (every checked answer)
    duplicates: int = 0    # pairs emitted twice in one answer
    answers: int = 0       # answers checked

    def __iadd__(self, other: "Tally") -> "Tally":
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))
        return self

    @property
    def recall(self) -> float:
        return self.found / self.wanted if self.wanted else 1.0

    def add(self, pairs: np.ndarray, ref: Reference, qmap: dict,
            X: np.ndarray, Y: np.ndarray) -> None:
        """Judge one answer. ``pairs`` holds (query, data) ids in ``X``;
        ``qmap`` maps the answer's checked query ids to their sample
        positions in ``ref``."""
        pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
        self.answers += 1
        self.beyond += beyond_theta(pairs, X, Y, ref.theta)
        self.duplicates += duplicates(pairs)
        if not qmap:
            return
        keys = np.fromiter(qmap.keys(), np.int64, len(qmap))
        sel = pairs[np.isin(pairs[:, 0], keys)]
        got = {(qmap[q], y) for q, y in sel.tolist()}
        pos = set(qmap.values())
        want = {p for p in ref.truth if p[0] in pos}
        self.found += len(got & want)
        self.wanted += len(want)
