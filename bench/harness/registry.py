"""Find the benchmark's parts by name. Each part is a file of its own under
``bench/``, so a later change adds a part by adding a file and an entry in
``BENCHMARK.json``, and edits no file that is already there:

  ``configs/<name>.json``   a deployment: sizes, θ, the ``regime`` that
                            draws its vectors and the ``metric`` whose
                            space judges them (``BENCHMARK.json``'s
                            ``configs``);
  ``traffic/<name>.json``   a traffic mix: the ``driver`` that runs it and
                            that driver's parameters;
  ``metrics/<name>.py``     a metric's reader, ``read(run) -> float | None``
                            (``harness.record.RunRecord``), ``None`` where
                            the run holds nothing to read;
  ``regimes/<name>.py``     a data regime, ``draw(cfg, n_data, n_query) ->
                            (Y, X)``: float32 rows and queries from the
                            configuration's ``shape_seed``
                            (``harness/data.py``);
  ``spaces/<metric>.py``    the reference's distance: the float64 test of
                            a block of pairs against θ, the test of a pair
                            beyond θ past its rounding band, the plain
                            row-wise distance, and the control's
                            lower-precision evaluation
                            (``harness/reference.py``);
  ``drivers/<name>.py``     a traffic driver, ``Driver(cell, seed,
                            seconds)`` with the steps that
                            ``harness/driver.py`` lists.

No part comes from a table in code: a name with no file is an error that
names the path."""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload with every part it names, resolved."""
    name: str
    chips: int
    cfg: dict                # the configuration, its name under "name"
    traffic: dict
    regime: ModuleType       # regimes/<cfg["regime"]>.py
    space: ModuleType        # spaces/<cfg["metric"]>.py
    driver: type             # drivers/<traffic["driver"]>.py's Driver


class Registry:
    """The benchmark rooted at ``root``: ``root/BENCHMARK.json`` and the
    parts under ``root/bench/``."""

    def __init__(self, root: str | Path):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    # -- BENCHMARK.json ------------------------------------------------------

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {[w['name'] for w in self.spec['workloads']]})")

    def end_to_end(self, cell: str) -> list[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", (cell,))]

    def per_layer(self, cell: str) -> list[dict]:
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose end-to-end metric it reports."""
        mine = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", (cell,)) and m["moves"] in mine]

    def cell(self, name: str) -> Cell:
        """The workload ``name`` with its configuration, traffic, regime,
        space and driver."""
        wl = self.workload(name)
        cfg, regime, space = self.deployment(wl["config"])
        traffic = self.traffic(wl["traffic"])
        driver = self._named("drivers", traffic, "driver",
                             f"bench/traffic/{wl['traffic']}.json").Driver
        return Cell(name, int(wl["chips"]), cfg, traffic, regime, space,
                    driver)

    def deployment(self, name: str) -> tuple[dict, ModuleType, ModuleType]:
        """The configuration ``name`` (with ``"name"`` set), its regime and
        its space."""
        cfg = dict(self.config(name), name=name)
        where = f"bench/configs/{name}.json"
        return (cfg, self._named("regimes", cfg, "regime", where),
                self._named("spaces", cfg, "metric", where))

    # -- parts ---------------------------------------------------------------

    def _json(self, kind: str, name: str) -> dict:
        path = self.dir / kind / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind[:-1]} file {path}")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                           f"table {sorted(table['devices'])}")
        return table["devices"][device_kind]

    def _named(self, kind: str, owner: dict, key: str, where: str
               ) -> ModuleType:
        """The part that ``owner[key]`` names. A missing key is an error
        that names the file it would choose; there is no default."""
        if key not in owner:
            raise KeyError(f"{where} has no {key!r} key, which names the "
                           f"file {self.dir / kind}/<{key}>.py")
        return self._module(kind, owner[key])

    def _module(self, kind: str, name: str) -> ModuleType:
        """The file ``kind/name.py``, loaded once per process, so that every
        registry of one root hands out the same module."""
        path = (self.dir / kind / f"{name}.py").resolve()
        key = "bench_part_" + hashlib.sha1(str(path).encode()).hexdigest()
        if key not in sys.modules:
            if not path.is_file():
                raise FileNotFoundError(f"no {kind[:-1]} file {path}")
            spec = importlib.util.spec_from_file_location(key, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            sys.modules[key] = mod
        return sys.modules[key]

    def metric(self, name: str) -> ModuleType:
        """A metric's reader: ``read(run) -> float | None``."""
        return self._module("metrics", name)

    def regime(self, name: str) -> ModuleType:
        """A data regime: ``draw(cfg, n_data, n_query) -> (Y, X)``."""
        return self._module("regimes", name)

    def space(self, name: str) -> ModuleType:
        """The reference's distance for a configuration's ``metric``."""
        return self._module("spaces", name)

    def driver(self, name: str) -> ModuleType:
        """A traffic driver's file; its ``Driver`` is what a run builds."""
        return self._module("drivers", name)
