"""Back-to-back one-shot joins: the cell's whole query set handed to
``engine.join`` again and again.

Traffic parameters: ``method`` and ``quant`` (the program's join preset and
storage mode), ``index`` (``"merged"`` builds G_(X u Y) in set-up; absent,
no index is built), ``check_queries`` (queries the reference judges) and
``trace_seconds`` (the traced run's window)."""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import time

import numpy as np

from harness.data import Deployment
from harness.driver import Call, annotate
from harness.reference import Tally, reference


class OneShot:
    """Back-to-back ``engine.join`` calls over the cell's query set, from
    the window's start until ``seconds`` have passed; the last call runs
    to its end. Each call returns host-side pairs, so its clock waits
    for the device."""
    build_s: float | None = None

    def __init__(self, cell, seed: int, seconds: float):
        self.cell = cell
        self.cfg, self.traffic = cell.cfg, cell.traffic
        self.seed, self.seconds = seed, seconds
        self.theta = float(self.cfg["theta"])
        self.calls: list[Call] = []

    def make_data(self) -> None:
        self.dep = Deployment(self.cfg, self.seed, self.cell.regime)
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
            ref = reference(self.X[sample], self.dep.Y, self.theta,
                            self.cell.space)
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


Driver = OneShot
