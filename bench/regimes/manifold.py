"""The ``manifold`` regime of ``data.vectors.make_dataset``: rows and
queries from one latent Gaussian pushed through a fixed random tanh network
of the configuration's ``latent`` width, with no ambient noise. The
queries are in distribution: the same generator, drawn after the rows."""
import numpy as np

from harness.data import ManifoldSampler


def draw(cfg: dict, n_data: int, n_query: int
         ) -> tuple[np.ndarray, np.ndarray]:
    sampler = ManifoldSampler(np.random.default_rng(cfg["shape_seed"]),
                              cfg["dim"], cfg["latent"])
    rng = np.random.default_rng([cfg["shape_seed"], 1])
    Y = sampler(rng, n_data)
    return Y, sampler(rng, n_query)
