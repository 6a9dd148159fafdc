"""Find the benchmark's parts by name: each configuration, traffic mix
and metric reader is a file of its own under ``bench/``, so a later
change adds a part by adding a file and an entry in ``BENCHMARK.json``,
and edits no file that is already there."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType


class Registry:
    """The benchmark rooted at ``root``: ``root/BENCHMARK.json`` and the
    parts under ``root/bench/``."""

    def __init__(self, root: str | Path):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict[Path, ModuleType] = {}

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

    def _module(self, kind: str, name: str) -> ModuleType:
        path = self.dir / kind / f"{name}.py"
        if path not in self._modules:
            if not path.is_file():
                raise FileNotFoundError(f"no {kind[:-1]} file {path}")
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name}".replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def metric(self, name: str) -> ModuleType:
        """A metric's reader: ``read(run) -> float | None``."""
        return self._module("metrics", name)
