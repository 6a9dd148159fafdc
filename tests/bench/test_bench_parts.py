"""Parts of a deployment found by file: a cell whose data regime, distance
space, configuration and traffic are all new files runs correct with no
edit to the harness; a space written by a test is the one the reference
uses; and the regimes and the L2 space that moved out of the harness draw
and judge exactly what they drew and judged before."""
import hashlib
import json
import time

import numpy as np
import pytest

from benchtiny import ROOT, make_tiny
from harness.data import Deployment, ManifoldSampler, thresholds
from harness.reference import beyond_theta, reference
from harness.registry import Registry

import run as bench_run

SEED = 2**31 + 11

OOD_REGIME = '''"""Rows from one tanh network, queries from another: the queries are
out of the table's distribution."""
import numpy as np

from harness.data import ManifoldSampler


def query_sampler(cfg):
    return ManifoldSampler(np.random.default_rng([cfg["shape_seed"], 2]),
                           cfg["dim"], cfg["latent"])


def draw(cfg, n_data, n_query):
    table = ManifoldSampler(np.random.default_rng(cfg["shape_seed"]),
                            cfg["dim"], cfg["latent"])
    rng = np.random.default_rng([cfg["shape_seed"], 1])
    Y = table(rng, n_data)
    return Y, query_sampler(cfg)(rng, n_query)
'''

# L2 by the difference form, where the shipped space uses the matmul form
L2_DIFF = '''import numpy as np


def within(xs, ys, theta):
    return np.sum((xs[:, None, :] - ys[None, :, :]) ** 2, axis=2) \\
        < theta * theta


def beyond(xs, ys, theta):
    band = 1e-6 * (np.sum(xs * xs, axis=1) + np.sum(ys * ys, axis=1))
    return np.sum((xs - ys) ** 2, axis=1) >= theta * theta + band


def distance(xs, ys):
    return np.sqrt(np.sum((xs - ys) ** 2, axis=1))
'''

INNER_PRODUCT = '''import numpy as np


def within(xs, ys, theta):
    return xs @ ys.T > theta


def band(xs, ys):
    return 1e-6 * np.linalg.norm(xs, axis=1) * np.linalg.norm(ys, axis=1)


def beyond(xs, ys, theta):
    return np.sum(xs * ys, axis=1) <= theta - band(xs, ys)


def distance(xs, ys):
    return -np.sum(xs * ys, axis=1)
'''


def _files(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_cell_of_new_files_only_runs_correct(tmp_path):
    root = make_tiny(tmp_path / "b")
    before = _files(root)
    bench = root / "bench"
    (bench / "regimes" / "crossmodal.py").write_text(OOD_REGIME)
    (bench / "spaces" / "l2diff.py").write_text(L2_DIFF)
    (bench / "traffic" / "probe.json").write_text(json.dumps(
        {"driver": "oneshot", "method": "nlj", "quant": "off",
         "check_queries": 48, "trace_seconds": 0.5}))
    cfg = {"dim": 16, "metric": "l2diff", "precision": "float32",
           "n_data": 1500, "n_query": 96, "regime": "crossmodal",
           "latent": 6, "shape_seed": 77, "recall_floor": 0.9,
           "engine": "default"}
    reg = Registry(root)
    regime = reg.regime("crossmodal")
    Y, X = regime.draw(cfg, cfg["n_data"], cfg["n_query"])
    cfg["theta"] = float(thresholds(X, Y, reg.space("l2diff"),
                                    sample=20_000)[3])
    (bench / "configs" / "crossmodal.json").write_text(json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "crossmodal.probe",
                              "config": "crossmodal", "traffic": "probe",
                              "chips": 1, "why": "new files only"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = Registry(root).cell("crossmodal.probe")
    drv = cell.driver(cell, SEED, 0.0)
    drv.make_data()
    # the queries come from the regime's second generator, not the table's
    table = ManifoldSampler(np.random.default_rng(cfg["shape_seed"]),
                            cfg["dim"], cfg["latent"])
    rng = np.random.default_rng([cfg["shape_seed"], 1])
    table(rng, cfg["n_data"])
    assert np.array_equal(drv.X, X)
    assert not np.allclose(X, table(rng, cfg["n_query"]), atol=0.1)

    out = bench_run.run_cell("crossmodal.probe", SEED, 0.5, False,
                             root=root, require_tpu=False,
                             t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["metrics"]["recall"]["value"] >= 0.9
    assert cell.space.__file__.endswith("l2diff.py")
    # no file that was there before was edited
    after = _files(root)
    assert {p: after[p] for p in before} == before


def test_space_written_by_a_test_is_the_references_distance(tmp_path):
    """Inner product on hand-made data: the reference's pairs and the
    pairs beyond θ are those of a brute-force loop."""
    root = make_tiny(tmp_path / "b")
    (root / "bench" / "spaces" / "ip.py").write_text(INNER_PRODUCT)
    ip = Registry(root).space("ip")
    X = np.array([[1.0, 0.0], [0.0, 2.0], [1.0, 1.0]], np.float32)
    Y = np.array([[0.9, 0.1], [0.2, 0.2], [0.0, 1.0], [-1.0, 3.0],
                  [0.4, 0.4]], np.float32)
    theta = 0.75
    dot = [[float(np.dot(x.astype(np.float64), y.astype(np.float64)))
            for y in Y] for x in X]
    loop = {(q, y) for q in range(len(X)) for y in range(len(Y))
            if dot[q][y] > theta}
    ref = reference(X, Y, theta, ip, block=2)
    assert ref.truth == loop and ref.space is ip
    # squared L2 would join other pairs: the space decides
    assert reference(X, Y, theta, Registry(ROOT).space("l2")).truth != loop
    pairs = np.array([[q, y] for q in range(len(X)) for y in range(len(Y))]
                     + [[0, 9]])
    far = sum(1 for q, y in pairs[:-1] if dot[q][y] <= theta - 1e-5)
    assert beyond_theta(pairs, X, Y, theta, ip) == far + 1


# Hashes (first 16 hex digits of SHA-256) and θ values of the tiny draws,
# taken with the harness of the commit before the regimes and spaces moved
# into files of their own (``harness.data.draw``, ``Deployment(cfg, seed)``,
# ``thresholds`` and ``reference`` of that commit), at the tiny
# configurations of ``benchtiny.TINY`` and the seed ``SEED``.
PARENT = {
    "sift1m": {"Y": "3ef54de1b9414606", "X": "a7d3aedc78d505b6",
               "Yseed": "a1124be1f5798179", "truth": "85dd191f1ecf035a",
               "n_truth": 1298,
               "th": ["1.244458794593811", "1.544872522354126",
                      "1.8452863693237305", "2.145699977874756",
                      "2.4461138248443604", "2.746527671813965",
                      "3.0469412803649902"]},
    "gist1m": {"Y": "a970e080191f9f89", "X": "2d375d78469634e3",
               "Yseed": "d0766a0ac92141b1", "truth": "238a66d3f6869d67",
               "n_truth": 13387,
               "th": ["3.110931158065796", "3.507375955581665",
                      "3.903820753097534", "4.300265312194824",
                      "4.696710109710693", "5.0931549072265625",
                      "5.489599704742432"]},
}


def _h(a):
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_moved_regimes_and_space_match_the_parent(tiny_root, name):
    cfg, regime, space = Registry(tiny_root).deployment(name)
    Y, X = regime.draw(cfg, cfg["n_data"], cfg["n_query"])
    ref = reference(X[:64], Y, cfg["theta"], space)
    got = {"Y": _h(Y), "X": _h(X),
           "Yseed": _h(Deployment(cfg, SEED, regime).Y),
           "truth": _h(np.array(sorted(ref.truth), np.int64)),
           "n_truth": len(ref.truth),
           "th": [repr(float(t)) for t in thresholds(X, Y, space)]}
    assert got == PARENT[name]

