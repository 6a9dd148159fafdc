"""Two modalities embedded in one space, in the shape of a text-to-image
deployment (big-ann-benchmarks' Yandex T2I): the table holds one
modality's embeddings (images), the queries come from the other (text)
and lie off the table's manifold.

  * Rows: the ``manifold`` generator at the configuration's ``latent``
    width, each row scaled by exp(``norm_spread``·N(0, 1)). Image
    embeddings' norms vary, and without a spread of norms an
    inner-product θ would be an L2 θ in disguise.
  * Queries, from a second generator: the same network's output for
    fresh latents, mapped by a fixed near-identity linear map
    I + ``text_map``·G/√d (G Gaussian, from ``[shape_seed, 2]``), then
    shifted by a fixed modality-gap vector whose length is ``gap`` times
    the rows' median norm (a random direction, from the same stream; the
    median over a fixed sample of 10,000 rows from ``[shape_seed, 3]``).

Rows and queries are drawn from ``shape_seed`` alone, from streams of
their own, so the queries are the same whatever ``n_data`` is."""
import numpy as np

from harness.data import ManifoldSampler


def _rows(net, rng, n, spread):
    Y = net(rng, n)
    Y *= np.exp(np.float32(spread)
                * rng.standard_normal((n, 1), dtype=np.float32))
    return Y


def draw(cfg: dict, n_data: int, n_query: int
         ) -> tuple[np.ndarray, np.ndarray]:
    seed, d = cfg["shape_seed"], cfg["dim"]
    net = ManifoldSampler(np.random.default_rng(seed), d, cfg["latent"])
    Y = _rows(net, np.random.default_rng([seed, 1]), n_data,
              cfg["norm_spread"])
    side = np.random.default_rng([seed, 2])
    M = np.eye(d, dtype=np.float32) + np.float32(cfg["text_map"] / np.sqrt(d)) \
        * side.standard_normal((d, d), dtype=np.float32)
    u = side.standard_normal(d, dtype=np.float32)
    sample = _rows(net, np.random.default_rng([seed, 3]), 10_000,
                   cfg["norm_spread"])
    gap = (np.float32(cfg["gap"])
           * np.median(np.linalg.norm(sample, axis=1)).astype(np.float32)
           * u / np.linalg.norm(u))
    X = net(np.random.default_rng([seed, 4]), n_query) @ M.T + gap
    return Y, np.ascontiguousarray(X, np.float32)
