"""The ``weak`` regime of ``data.vectors.make_dataset``: the manifold
generator at twice the configuration's ``latent`` width (at least 12), with
ambient Gaussian noise of standard deviation 0.05 on the rows and 0.08 on
the queries, so locality is weak. The queries are in distribution: the same
generator, drawn after the rows."""
import numpy as np

from harness.data import ManifoldSampler


def draw(cfg: dict, n_data: int, n_query: int
         ) -> tuple[np.ndarray, np.ndarray]:
    sampler = ManifoldSampler(np.random.default_rng(cfg["shape_seed"]),
                              cfg["dim"], max(cfg["latent"] * 2, 12))
    rng = np.random.default_rng([cfg["shape_seed"], 1])
    Y = sampler(rng, n_data, 0.05)
    return Y, sampler(rng, n_query, 0.08)
