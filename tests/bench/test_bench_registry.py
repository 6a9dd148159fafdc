"""The registry finds every part of the benchmark by name, so a later
change adds a configuration, traffic mix or metric by adding a file and
an entry, with no edit to the harness."""
import json

import pytest

from harness.drivers import DRIVERS
from harness.record import RunRecord
from harness.registry import Registry


def test_real_benchmark_parts_all_resolve():
    from benchtiny import ROOT
    reg = Registry(ROOT)
    for w in reg.spec["workloads"]:
        reg.config(w["config"])
        tr = reg.traffic(w["traffic"])
        assert tr["driver"] in DRIVERS
        assert reg.end_to_end(w["name"]), w["name"]
        assert reg.per_layer(w["name"]), w["name"]
        for m in reg.end_to_end(w["name"]) + reg.per_layer(w["name"]):
            assert callable(reg.metric(m["name"]).read)
    assert reg.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error(tiny_root):
    with pytest.raises(KeyError, match="peaks table"):
        Registry(tiny_root).peaks("TPU v99")


@pytest.mark.parametrize("part", ["config", "traffic", "metric"])
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
