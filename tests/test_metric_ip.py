"""Inner-product joins (``metric="ip"``: θ bounds −⟨x, y⟩) on the normal
path — ``JoinEngine.join``/``submit`` and ``JoinService`` — held to the
plain float64 reference (``core.join.exact_ip_pairs``) up to its rounding
band (``theta_band(..., "ip")``), on a cross-modal draw (queries across a
modality gap from the rows) and an in-distribution one."""
import dataclasses

import numpy as np
import pytest

from repro.configs.vectorjoin import make_engine
from repro.core.graph import build_index
from repro.core.join import exact_ip_pairs, theta_band
from repro.core.types import (METHODS, GraphIndex, JoinConfig, JoinStats,
                              TraversalConfig)
from repro.engine import JoinEngine
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.serve.join_service import JoinRequest, JoinService

TC = TraversalConfig(beam_width=64, expand_per_iter=4, pool_cap=1024,
                     hybrid_beam=64, seeds_max=8, max_iters=2048)
BK = dict(k=32, degree=16)
SERVABLE = ("nlj", "index", "es", "es_hws", "es_sws")


def _draw(crossmodal: bool, n=2000, nq=96, d=32, seed=3):
    """Rows from a tanh network with spread norms; queries from the same
    network, or (cross-modal) mapped by a near-identity map and shifted by
    a gap of half the rows' median norm."""
    rng = np.random.default_rng(seed)
    W1 = rng.normal(size=(8, 64)).astype(np.float32)
    W2 = (rng.normal(size=(64, d)) / 8).astype(np.float32)

    def net(m):
        return np.tanh(rng.normal(size=(m, 8)).astype(np.float32) @ W1) @ W2
    Y = net(n) * np.exp(0.25 * rng.normal(size=(n, 1))).astype(np.float32)
    X = net(nq)
    if crossmodal:
        M = np.eye(d) + 0.5 / np.sqrt(d) * rng.normal(size=(d, d))
        u = rng.normal(size=d)
        gap = 0.5 * np.median(np.linalg.norm(Y, axis=1)) * u / np.linalg.norm(u)
        X = X @ M.T + gap
    return Y.astype(np.float32), X.astype(np.float32)


@dataclasses.dataclass
class Case:
    Y: np.ndarray
    X: np.ndarray
    theta: float
    truth: set
    band: set
    eng: JoinEngine

    def cfg(self, method, **kw):
        return JoinConfig(method=method, theta=self.theta, traversal=TC,
                          wave_size=64, **kw)

    def check(self, pairs, method, floor=0.8):
        got = set(map(tuple, np.asarray(pairs).tolist()))
        assert len(got) == len(pairs), "duplicate pairs"
        assert got <= self.truth | self.band, sorted(got - self.truth)[:5]
        if method == "nlj":
            assert self.truth - self.band <= got
        else:
            assert len(got & self.truth) >= floor * len(self.truth)
        return len(got & self.truth) / len(self.truth)


@pytest.fixture(scope="module", params=["crossmodal", "in_distribution"])
def case(request):
    Y, X = _draw(request.param == "crossmodal")
    # the 2e-3 quantile of −⟨x, y⟩: negative, about 4 pairs a query
    theta = float(np.quantile(-(X.astype(np.float64) @ Y.T), 2e-3))
    assert theta < 0
    truth = set(map(tuple, exact_ip_pairs(X, Y, theta).tolist()))
    eng = JoinEngine(Y, build_kw=BK, metric="ip",
                     metrics=obs_metrics.Metrics())
    return Case(Y, X, theta, truth, theta_band(X, Y, theta, "ip"), eng)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("kernel,shape", [
    ("pairwise", (13, 700, 200)), ("pairwise", (9, 130, 33)),
    ("pairwise", (1, 1, 1)), ("pairwise", (0, 5, 4)),
    ("pairwise", (2, 3, 0)),
    ("rowwise", (13, 37, 200)), ("rowwise", (4, 96, 64)),
    ("rowwise", (1, 1, 1)), ("rowwise", (2, 0, 4)),
])
def test_ip_kernels_match_numpy(kernel, shape, impl):
    """−⟨x, y⟩ inside the rounding band the float64 reference grants a
    float32 dot, padded shapes and empty inputs included."""
    from repro.core.join import DOT_GUARD
    rng = np.random.default_rng(sum(shape))
    B, n, d = shape
    x = rng.normal(size=(B, d)).astype(np.float32)
    x64 = x.astype(np.float64)
    if kernel == "pairwise":
        y = rng.normal(size=(n, d)).astype(np.float32).astype(np.float64)
        got = ops.pairwise_neg_ip(x, y.astype(np.float32), impl=impl)
        want = -(x64 @ y.T)
        scale = np.outer(np.linalg.norm(x64, axis=1),
                         np.linalg.norm(y, axis=1))
    else:
        c = rng.normal(size=(B, n, d)).astype(np.float32).astype(np.float64)
        got = ops.rowwise_neg_ip(x, c.astype(np.float32), impl=impl)
        want = -np.einsum("bd,bkd->bk", x64, c)
        scale = (np.linalg.norm(x64, axis=1)[:, None]
                 * np.linalg.norm(c, axis=2))
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.all(np.abs(np.asarray(got, np.float64) - want)
                  <= DOT_GUARD * scale)


@pytest.mark.parametrize("path", ["join", "submit"])
@pytest.mark.parametrize("method", METHODS)
def test_engine_matches_reference(case, method, path):
    if path == "join":
        res = case.eng.join(case.X, case.cfg(method))
    else:
        case.eng.reset_stream()
        res = case.eng.submit(case.X, case.cfg(method))
    case.check(res.pairs, method)


@pytest.mark.parametrize("method", SERVABLE)
def test_service_matches_reference(case, method):
    svc = JoinService(metrics=obs_metrics.Metrics())
    svc.load("t", case.Y, build_kw=BK, default=case.cfg(method),
             engine_kw={"metric": "ip"})
    assert svc.submit(JoinRequest(uid=1, tenant="t", X=case.X,
                                  theta=case.theta, method=method,
                                  quant="off"))
    served = svc.run()[1]
    assert served.ok, svc.failed
    case.check(np.array(sorted(served.pair_set_local())), method)


def test_adapt_routes_crossmodal_queries_to_the_hybrid_path():
    """On the cross-modal draw queries are predicted OOD, and the hybrid
    path they take finds at least what the BFS path finds."""
    Y, X = _draw(True)
    theta = float(np.quantile(-(X.astype(np.float64) @ Y.T), 2e-3))
    truth = set(map(tuple, exact_ip_pairs(X, Y, theta).tolist()))
    eng = JoinEngine(Y, build_kw=BK, metric="ip",
                     metrics=obs_metrics.Metrics())
    cfg = JoinConfig(method="es_mi", theta=theta, traversal=TC,
                     wave_size=64)
    mi = eng.join(X, cfg)
    ad = eng.join(X, dataclasses.replace(cfg, method="es_mi_adapt"))
    rec = [len(r.pair_set() & truth) / len(truth) for r in (mi, ad)]
    assert rec[1] >= rec[0], rec
    assert mi.stats.n_hybrid_lane_iters == 0 and mi.stats.n_ood == 0
    assert ad.stats.n_ood > 0
    assert 0 < ad.stats.n_hybrid_lane_iters <= ad.stats.n_lane_iters


def _refusals(Y, X):
    ip = dict(metric="ip", metrics=obs_metrics.Metrics())
    sq8 = JoinConfig(method="es_mi", theta=-1.0, quant="sq8")

    def service():
        svc = JoinService(metrics=obs_metrics.Metrics())
        svc.load("t", Y, build_kw=BK, engine_kw={"metric": "ip"})
        assert not svc.submit(JoinRequest(uid=1, tenant="t", X=X,
                                          theta=-1.0, method="es",
                                          quant="sq8"))
        raise ValueError(svc.failed[1])
    return {
        "mesh": lambda: JoinEngine(Y, n_shards=2, **ip),
        "default_quant": lambda: JoinEngine(Y, default=sq8, **ip),
        "build_quant": lambda: JoinEngine(Y, build_kw={"quant": "sq8"},
                                          **ip),
        "join_quant": lambda: JoinEngine(Y, build_kw=BK, **ip).join(
            X, dataclasses.replace(sq8, quant="sketch8")),
        "submit_quant": lambda: JoinEngine(Y, build_kw=BK, **ip).submit(
            X, dataclasses.replace(sq8, method="nlj", quant="pdx8")),
        "make_engine": lambda: make_engine(Y, "ip", quant="sq8"),
        "cascade_build": lambda: build_index(Y, metric="ip", quant="sq8"),
        "service": service,
    }


@pytest.mark.parametrize("kind", ["mesh", "default_quant", "build_quant",
                                  "join_quant", "submit_quant",
                                  "make_engine", "cascade_build",
                                  "service"])
def test_ip_refuses_what_assumes_l2(kind):
    """Quant modes other than off certify L2 bounds, and the mesh driver
    is L2: under ip they raise (the service rejects the request) and
    never fall back to L2."""
    Y, X = _draw(True, n=300, nq=16)
    with pytest.raises(ValueError, match="ip"):
        _refusals(Y, X)[kind]()


def test_metric_is_the_tables():
    Y, X = _draw(False, n=300, nq=16)
    assert JoinEngine(Y).metric == "l2"
    assert build_index(Y, **BK).metric == "l2"
    g = build_index(Y, metric="ip", **BK)
    assert isinstance(g, GraphIndex) and g.metric == "ip"
    with pytest.raises(ValueError, match="metric"):
        JoinEngine(Y).adopt(index_y=g)
    with pytest.raises(ValueError, match="unknown metric"):
        JoinEngine(Y, metric="cosine")
    # the ip engine builds its artifacts in its metric
    eng = JoinEngine(Y, build_kw=BK, metric="ip")
    assert eng.index_y().metric == eng.merged_index(X).metric == "ip"
    assert JoinStats().n_hybrid_lane_iters == 0


def test_ip_graph_repair_converges(monkeypatch):
    """Under ip the prune leaves many nodes with no in-edge; the repair
    (in L2, core/graph.py) attaches them in one round, and every node of
    the merged index is reachable from the navigating node."""
    from repro.core import graph
    Y, X = _draw(True)
    seen = []
    reach = graph._reachable
    monkeypatch.setattr(graph, "_reachable",
                        lambda nbrs, start: seen.append(
                            reach(nbrs, start)) or seen[-1])
    g = build_index(np.concatenate([Y, X]), n_data=len(Y), metric="ip",
                    **BK)
    assert not seen[0].all(), "the draw should need a repair"
    assert len(seen) == 2 and seen[1].all()
    assert reach(np.asarray(g.nbrs), int(g.start)).all()


def test_ip_rows_keep_their_pruned_candidates():
    """Under ip each row's free slots take the candidates its prune
    dropped, nearest first, and every edge it had stays."""
    from repro.core.graph import _fill_pruned
    N = -1
    nbrs = np.array([[5, N, 7, N], [1, 2, 3, 4], [N, N, N, N]], np.int32)
    cand = np.array([[5, 9, 7, 8, 6], [9, 8, 7, 6, 5], [3, N, 4, 5, 6]],
                    np.int32)
    np.testing.assert_array_equal(
        _fill_pruned(nbrs.copy(), cand),
        [[5, 9, 7, 8], [1, 2, 3, 4], [3, 4, 5, 6]])
    Y, X = _draw(True, n=600, nq=32)
    g_ip = np.asarray(build_index(Y, metric="ip", **BK).nbrs)
    assert (g_ip >= 0).all(axis=1).mean() > 0.9   # rows come out full


def test_ip_index_does_not_depend_on_row_order():
    """The ip build runs in a canonical row order: a table given in
    another order gets the same graph, relabelled."""
    Y, X = _draw(True, n=800, nq=48)
    p = np.random.default_rng(5).permutation(len(Y))
    a = build_index(np.concatenate([Y, X]), n_data=len(Y), metric="ip", **BK)
    b = build_index(np.concatenate([Y[p], X]), n_data=len(Y), metric="ip",
                    **BK)
    ids = np.concatenate([p, np.arange(len(Y), len(Y) + len(X))])
    nb = np.asarray(b.nbrs)
    np.testing.assert_array_equal(np.where(nb >= 0, ids[nb], nb),
                                  np.asarray(a.nbrs)[ids])
    assert ids[int(b.start)] == int(a.start)
    np.testing.assert_allclose(np.asarray(b.mean_nbr_dist),
                               np.asarray(a.mean_nbr_dist)[ids], rtol=1e-6)
