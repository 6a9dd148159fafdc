"""The general traffic generator. A traffic file names it by its
``driver`` key and gives its parameters; adding a mix is adding a file.

A driver has four steps, which ``run.py`` calls in order:

  ``setup()``   data from the seed, the program's state, the index build
                and a warm-up of exactly the window's shapes;
  ``window()``  the measured traffic (nothing compiles here);
  ``release()`` frees the program's state once ``memory_peak_bytes`` is
                read, so the reference does not set the peak;
  ``check()``   the plain reference over a sample drawn from the seed,
                against every answer due in the window.

The program is driven only through ``make_engine`` and ``JoinEngine``,
with inputs generated here.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import time

import numpy as np

from harness.data import Deployment
from harness.reference import Tally, reference


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Call:
    """One timed one-shot join."""
    t0: float
    t1: float
    n_queries: int
    stats: object          # the program's JoinStats of the call
    pairs: np.ndarray


class OneShot:
    """Back-to-back ``engine.join`` calls over the cell's query set, from
    the window's start until ``seconds`` have passed; the last call runs
    to its end. Each call returns host-side pairs, so its clock waits
    for the device."""
    build_s: float | None = None

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds = seed, seconds
        self.theta = float(cfg["theta"])
        self.calls: list[Call] = []

    def make_data(self) -> None:
        self.dep = Deployment(self.cfg, self.seed)
        self.X = self.dep.X

    def join_config(self):
        from repro.configs.vectorjoin import preset
        jcfg = preset(self.traffic["method"], theta=self.theta)
        return dataclasses.replace(jcfg, quant=self.traffic["quant"])

    def setup(self) -> None:
        import jax

        from repro.configs.vectorjoin import make_engine
        self.make_data()
        self.jcfg = self.join_config()
        self.eng = make_engine(self.dep.Y, self.cfg["engine"],
                               default=self.jcfg)
        if self.traffic.get("index") == "merged":
            t0 = time.perf_counter()
            with annotate("bench.build"):
                m = self.eng.merged_index(self.X)
                jax.block_until_ready(m.nbrs)
            self.build_s = time.perf_counter() - t0
        with annotate("bench.warmup"):
            self.eng.join(self.X, self.jcfg)

    def window(self) -> None:
        t_end = time.perf_counter() + self.seconds
        while True:
            t0 = time.perf_counter()
            with annotate("bench.engine.join"):
                res = self.eng.join(self.X, self.jcfg)
            t1 = time.perf_counter()
            self.calls.append(Call(t0, t1, len(self.X), res.stats,
                                   np.asarray(res.pairs)))
            if t1 >= t_end:
                break

    def release(self) -> None:
        del self.eng
        gc.collect()

    def check(self) -> Tally:
        n = len(self.X)
        k = min(int(self.traffic["check_queries"]), n)
        rng = np.random.default_rng([self.seed, 1])
        sample = np.sort(rng.choice(n, size=k, replace=False))
        with annotate("bench.reference"):
            ref = reference(self.X[sample], self.dep.Y, self.theta)
        qmap = {int(q): i for i, q in enumerate(sample)}
        tally, judged = Tally(), {}
        for c in self.calls:
            key = hashlib.sha1(c.pairs.tobytes()).hexdigest() + \
                str(c.pairs.shape)
            if key not in judged:
                one = Tally()
                one.add(c.pairs, ref, qmap, self.X, self.dep.Y)
                judged[key] = one
            tally += judged[key]
        return tally


DRIVERS = {"oneshot": OneShot}
