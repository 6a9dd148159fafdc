"""The plain reference: exact float64 threshold join on the host.

The distance is the configuration's: its ``metric`` key names a file
``bench/spaces/<metric>.py`` (``Registry.deployment``) that defines

  ``within(xs, ys, theta)``   the (len(xs), len(ys)) mask of the pairs of
                              two float64 blocks that join;
  ``beyond(xs, ys, theta)``   for matched float64 rows, the pairs that lie
                              beyond θ past the rounding band, the width of
                              which (``band``) any float32 evaluation may
                              round either way;
  ``distance(xs, ys)``        each matched pair's distance, in the rows'
                              own precision (θ's calibration,
                              ``harness/data.py``);
  ``low_table(Yl)``,          the control's evaluation one precision below
  ``low_within(xl, table, theta)``  the configuration's
                              (``harness/control.py``).

The benchmark's parts are files under ``bench/``: ``configs/``,
``traffic/``, ``metrics/``, ``regimes/``, ``spaces/`` and ``drivers/``;
``harness/registry.py`` says what each file defines.

A space has no default: a configuration without ``metric``, or one that
names no file, is an error. Nothing here shares code with the join.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Reference:
    """Exact pairs of the sample queries. Query ids are positions in the
    sample (0 .. len(sample) - 1)."""
    theta: float
    truth: set          # (q, y) that join in float64
    space: object       # the spaces/<metric>.py module that judged them


def reference(Xs: np.ndarray, Y: np.ndarray, theta: float, space,
              block: int = 32768) -> Reference:
    """Exact join of the sample queries ``Xs`` against all of ``Y`` in the
    space's distance."""
    xs = Xs.astype(np.float64)
    truth = set()
    for y0 in range(0, Y.shape[0], block):
        yb = Y[y0:y0 + block].astype(np.float64)
        qi, yi = np.nonzero(space.within(xs, yb, theta))
        truth.update(zip(qi.tolist(), (yi + y0).tolist()))
    return Reference(theta, truth, space)


def beyond_theta(pairs: np.ndarray, X: np.ndarray, Y: np.ndarray,
                 theta: float, space, block: int = 1 << 17) -> int:
    """Emitted pairs that lie beyond θ past the band in float64, or whose
    ids lie outside ``X``/``Y`` (an answer that names no row is as wrong
    as one beyond θ)."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    ok = ((pairs[:, 0] >= 0) & (pairs[:, 0] < X.shape[0])
          & (pairs[:, 1] >= 0) & (pairs[:, 1] < Y.shape[0]))
    bad = int(np.count_nonzero(~ok))
    pairs = pairs[ok]
    for p0 in range(0, len(pairs), block):
        q, y = pairs[p0:p0 + block, 0], pairs[p0:p0 + block, 1]
        xq, yy = X[q].astype(np.float64), Y[y].astype(np.float64)
        bad += int(np.count_nonzero(space.beyond(xq, yy, theta)))
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
        self.beyond += beyond_theta(pairs, X, Y, ref.theta, ref.space)
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
