"""Seeded vector data for the benchmark's deployments.

A configuration's ``regime`` key names a file ``bench/regimes/<name>.py``
that defines ``draw(cfg, n_data, n_query) -> (Y, X)``: ``n_data`` float32
table rows and ``n_query`` float32 queries, drawn from the configuration's
fixed ``shape_seed`` and nothing else. A regime may draw the queries from
another generator than the rows (an out-of-distribution deployment). Its
``metric`` key names the space (``harness/reference.py``) whose distance
calibrates θ here.

The benchmark's parts are files under ``bench/``: ``configs/``,
``traffic/``, ``metrics/``, ``regimes/``, ``spaces/`` and ``drivers/``;
``harness/registry.py`` says what each file defines.

``ManifoldSampler`` and the threshold rule are copied from
``src/repro/data/vectors.py`` at commit cb1b0a7 (the ``manifold`` and
``weak`` regimes of ``make_dataset`` and ``thresholds``), so that a change
to the program's generator cannot move the yardstick. Two departures from
the original, both for run-to-run steadiness:

  * the generator network, the rows and the queries are drawn from the
    configuration's fixed ``shape_seed``; the run's ``--seed`` only
    orders the table's rows. Every seed then joins the same vectors in
    the same waves of queries, so the threshold and the join's work do
    not swing from seed to seed (with rows drawn from the seed, the
    traversal's iterations swung by a quarter between seeds at 20,000
    rows of the GIST shape, and with the queries' order drawn from it,
    by an eighth);
  * rows and noise are drawn as float32 (``standard_normal(dtype=...)``
    times the original's standard deviation), which halves the host
    memory of the GIST-shaped table and its set-up time.
"""
from __future__ import annotations

import numpy as np


class ManifoldSampler:
    """Latent Gaussian pushed through a fixed random tanh network
    (``data.vectors._manifold_sampler``), plus optional ambient noise
    (the ``weak`` regime)."""

    def __init__(self, shape_rng: np.random.Generator, dim: int,
                 latent: int, hidden: int = 64):
        self.W1 = shape_rng.normal(0, 1.0, (latent, hidden)).astype(
            np.float32)
        self.W2 = (shape_rng.normal(0, 1.0, (hidden, dim))
                   / np.sqrt(hidden)).astype(np.float32)

    def __call__(self, rng: np.random.Generator, n: int,
                 noise: float = 0.0) -> np.ndarray:
        z = rng.standard_normal((n, self.W1.shape[0]), dtype=np.float32)
        out = np.tanh(z @ self.W1) @ self.W2
        if noise:
            out += np.float32(noise) * rng.standard_normal(
                out.shape, dtype=np.float32)
        return np.ascontiguousarray(out, np.float32)


class Deployment:
    """The table ``Y`` and the query set ``X`` of one configuration: its
    regime's vectors, the table's rows in the order the run's seed
    gives."""

    def __init__(self, cfg: dict, seed: int, regime):
        Y, self.X = regime.draw(cfg, cfg["n_data"], cfg["n_query"])
        self.Y = Y[np.random.default_rng(seed).permutation(len(Y))]


def thresholds(X: np.ndarray, Y: np.ndarray, space, n: int = 7, *,
               lo_q: float = 1e-4, hi_q: float = 5e-2,
               sample: int = 200_000, seed: int = 0,
               block: int = 1 << 16) -> np.ndarray:
    """n evenly spaced thresholds spanning sparse→dense joins (the
    paper's Table 2), from the empirical query-to-data distribution of
    the space's distance (``data.vectors.thresholds``, in-distribution
    quantiles)."""
    rng = np.random.default_rng(seed)
    qi = rng.integers(0, X.shape[0], sample)
    yi = rng.integers(0, Y.shape[0], sample)
    d = np.concatenate([
        space.distance(X[qi[i:i + block]], Y[yi[i:i + block]])
        for i in range(0, sample, block)])
    return np.linspace(np.quantile(d, lo_q), np.quantile(d, hi_q),
                       n).astype(np.float64)


def calibrate_theta(cfg: dict, regime, space, *, n_query: int = 20_000,
                    n_data: int = 200_000, sample: int = 2_000_000) -> float:
    """θ₁ of the deployment's distribution: the first of the seven
    Table-2 thresholds over a calibration draw from ``shape_seed``, with
    ten times the original's pair sample so that the quantile is steady.
    The configuration file records the value; ``tools/calibrate.py``
    recomputes it."""
    Y, X = regime.draw(cfg, n_data, n_query)
    return float(thresholds(X, Y, space, sample=sample)[0])
