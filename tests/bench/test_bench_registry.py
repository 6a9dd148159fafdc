"""The registry finds every part of the benchmark by name, so a later
change adds a configuration, traffic mix, metric, data regime, distance
space or traffic driver by adding a file and an entry, with no edit to the
harness."""
import json

import numpy as np
import pytest

from harness.record import RunRecord
from harness.registry import Registry


def test_real_benchmark_parts_all_resolve():
    from benchtiny import ROOT
    reg = Registry(ROOT)
    for w in reg.spec["workloads"]:
        cell = reg.cell(w["name"])
        assert callable(cell.regime.draw) and callable(cell.space.within)
        assert callable(cell.driver)
        assert reg.end_to_end(w["name"]), w["name"]
        assert reg.per_layer(w["name"]), w["name"]
        for m in reg.end_to_end(w["name"]) + reg.per_layer(w["name"]):
            assert callable(reg.metric(m["name"]).read)
    assert reg.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error(tiny_root):
    with pytest.raises(KeyError, match="peaks table"):
        Registry(tiny_root).peaks("TPU v99")


@pytest.mark.parametrize("part", ["config", "traffic", "metric", "regime",
                                  "space", "driver"])
def test_added_file_is_found_without_code_edit(tmp_path, part):
    from benchtiny import make_tiny
    root = make_tiny(tmp_path / "b")
    reg = Registry(root)
    if part == "config":
        (root / "bench/configs/newdeploy.json").write_text(
            json.dumps({"dim": 7, "theta": 1.0}))
        assert reg.config("newdeploy")["dim"] == 7
    elif part == "traffic":
        (root / "bench/traffic/newmix.json").write_text(
            json.dumps({"driver": "oneshot", "method": "nlj"}))
        assert reg.traffic("newmix")["method"] == "nlj"
    elif part == "metric":
        (root / "bench/metrics/new.metric.py").write_text(
            "def read(run):\n    return 42.0\n")
        assert reg.metric("new.metric").read(None) == 42.0
    elif part == "regime":
        (root / "bench/regimes/ones.py").write_text(
            "import numpy as np\n"
            "def draw(cfg, n_data, n_query):\n"
            "    return (np.ones((n_data, cfg['dim']), np.float32),\n"
            "            np.zeros((n_query, cfg['dim']), np.float32))\n")
        Y, X = reg.regime("ones").draw({"dim": 3}, 5, 2)
        assert Y.shape == (5, 3) and X.shape == (2, 3)
    elif part == "space":
        (root / "bench/spaces/linf.py").write_text(
            "import numpy as np\n"
            "def distance(xs, ys):\n"
            "    return np.max(np.abs(xs - ys), axis=1)\n")
        d = reg.space("linf").distance(np.array([[0.0, 3.0]]),
                                       np.array([[1.0, 1.0]]))
        assert d.tolist() == [2.0]
    elif part == "driver":
        (root / "bench/drivers/idle.py").write_text(
            "class Driver:\n"
            "    def __init__(self, cell, seed, seconds):\n"
            "        self.seed = seed\n")
        assert reg.driver("idle").Driver(None, 5, 1.0).seed == 5


def test_new_cell_and_metric_entries_select_by_workload(tmp_path):
    from benchtiny import make_tiny
    root = make_tiny(tmp_path / "b")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "gist1m.nlj", "config": "gist1m",
                              "traffic": "nlj", "chips": 1, "why": "x"})
    spec["end_to_end"].append({"name": "new_p95_ms", "unit": "ms",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["gist1m.nlj"]})
    spec["per_layer"].append({"name": "new.metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "kernels", "moves": "new_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    reg = Registry(root)
    assert reg.workload("gist1m.nlj")["traffic"] == "nlj"
    e2e = {m["name"] for m in reg.end_to_end("gist1m.nlj")}
    assert {"join_qps", "new_p95_ms"} <= e2e
    assert "new_p95_ms" not in {m["name"]
                                for m in reg.end_to_end("sift1m.join")}
    # a per-layer metric without a workloads list goes to every cell that
    # reports the end-to-end metric it moves, and to no other
    assert "new.metric" in {m["name"] for m in reg.per_layer("gist1m.nlj")}
    assert "new.metric" not in {m["name"]
                                for m in reg.per_layer("sift1m.join")}


def test_metric_reader_that_finds_nothing_returns_none(tiny_root):
    reg = Registry(tiny_root)
    rec = RunRecord("sift1m.join", {}, {}, None, 1.0, 0, None)
    for name in ("idle_share.join", "hbm_peak_mb"):
        assert reg.metric(name).read(rec) is None, name


@pytest.mark.parametrize("key,kind,owner", [
    ("metric", "spaces", "configs/sift1m.json"),
    ("regime", "regimes", "configs/sift1m.json"),
    ("driver", "drivers", "traffic/join.json")])
@pytest.mark.parametrize("fault", ["no key", "no file"])
def test_part_without_a_file_is_an_error_naming_its_path(tmp_path, key,
                                                         kind, owner, fault):
    """A configuration without ``metric`` never falls back to L2, and no
    other part has a default either: the error names the file."""
    from benchtiny import make_tiny
    root = make_tiny(tmp_path / "b")
    path = root / "bench" / owner
    part = json.loads(path.read_text())
    if fault == "no key":
        del part[key]
    else:
        part[key] = "nowhere"
    path.write_text(json.dumps(part))
    err = KeyError if fault == "no key" else FileNotFoundError
    with pytest.raises(err) as got:
        Registry(root).cell("sift1m.join")
    want = (f"{kind}/<{key}>.py" if fault == "no key"
            else f"{kind}/nowhere.py")
    assert want in str(got.value)
