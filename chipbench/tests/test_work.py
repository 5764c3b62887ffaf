"""Least-work counts, by hand on the paper's Fig. 1 graph."""
import numpy as np
import pytest

from chipbench import gen, reference, work
from repro.graph import generate
from repro.graph.csr import transpose


def test_pagerank_least_bytes_by_hand():
    g = generate.two_level_example()         # 6 vertices, 13 edges
    # per iteration: 13 source ids x 4 B + 6 vertices x 16 B = 148 B
    assert work.pagerank_least_bytes(g.num_nodes, g.num_edges, 1) == 148
    assert work.pagerank_least_bytes(g.num_nodes, g.num_edges, 10) == 1480


def test_sssp_least_bytes_by_hand():
    g_out = transpose(generate.two_level_example())
    out_deg = np.diff(g_out.indptr)
    np.testing.assert_array_equal(out_deg, [2, 1, 4, 1, 1, 4])
    # vertices 3 and 5 only: 1 + 4 out-edges x 8 B + 2 x 12 B = 64 B
    reached = np.zeros(6, bool)
    reached[[3, 5]] = True
    assert work.sssp_least_bytes(out_deg, reached) == 64
    # from vertex 4 Dijkstra reaches all six: 13 x 8 + 6 x 12 = 176 B
    dist = reference.dijkstra(g_out.indptr, g_out.indices,
                              np.ones(g_out.num_edges), 6, [4])[0]
    assert np.isfinite(dist).all()
    assert work.sssp_least_bytes(out_deg, np.isfinite(dist)) == 176


def test_hbm_roofline_share():
    class S:
        busy_s = [2.0]
    ctx = {"trace": S(), "job": "pagerank", "work": {"least_bytes": 819e6},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    # 819 MB in 2 s of busy time at 819 GB/s: 1 ms of 2 s = 0.05%
    assert abs(work.hbm_roofline(ctx, "pagerank") - 0.05) < 1e-12
    assert work.hbm_roofline(ctx, "sssp") is None
    assert work.hbm_roofline({**ctx, "trace": None}, "pagerank") is None


def test_sssp_round_ms():
    from chipbench import run

    reader = run.load_module(run.BENCH / "metrics" / "sssp.round_ms.py")

    class S:
        busy_s = [3.0]
    ctx = {"trace": S(), "job": "sssp", "work": {"rounds": 24}}
    assert reader.read(ctx) == 125.0          # 3 s over 24 rounds
    assert reader.read({**ctx, "job": "pagerank"}) is None
    assert reader.read({**ctx, "trace": None}) is None
    S.busy_s = [0.0]
    assert reader.read(ctx) is None           # no device time: no reading


def _frontier_bellman_ford(indptr, indices, weights, n, source):
    """Distances and the rounds a synchronous frontier Bellman-Ford runs,
    the loop ``repro.apps.sssp`` runs, written out in numpy."""
    row = np.repeat(np.arange(n), np.diff(indptr))
    dist = np.full(n, np.inf)
    dist[source] = 0
    active = np.zeros(n, bool)
    active[source] = True
    rounds = 0
    while active.any():
        cand = np.where(active[row], dist[row] + weights, np.inf)
        best = np.full(n, np.inf)
        np.minimum.at(best, indices, cand)
        active = best < dist
        dist = np.minimum(dist, best)
        rounds += 1
    return dist, rounds


def test_bellman_ford_rounds_by_hand():
    g_out = transpose(generate.two_level_example())
    w = np.ones(g_out.num_edges)
    # unit weights from vertex 4: vertex 0 lies 4 arcs away, the farthest,
    # so 4 rounds change something and a fifth changes nothing
    dist = reference.dijkstra(g_out.indptr, g_out.indices, w, 6, [4])[0]
    np.testing.assert_array_equal(dist, [4, 3, 3, 1, 0, 2])
    assert reference.bellman_ford_rounds(g_out.indptr, g_out.indices, w,
                                         dist, 4) == 5


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bellman_ford_rounds_match_the_frontier_loop(seed):
    g, m, _ = gen.build({"scale": 10, "edge_factor": 16, "a": 0.57,
                         "b": 0.19, "c": 0.19, "weights": [1, 255],
                         "symmetric": True}, seed, "out")
    indptr, indices, weights = (np.asarray(a) for a in
                                (g.indptr, g.indices[:m], g.weights[:m]))
    n = g.num_nodes
    for source in np.flatnonzero(np.diff(indptr) > 0)[:4]:
        dist, rounds = _frontier_bellman_ford(indptr, indices, weights, n,
                                              source)
        ref = reference.dijkstra(indptr, indices, weights, n, [source])[0]
        np.testing.assert_array_equal(dist, ref)
        assert reference.bellman_ford_rounds(indptr, indices, weights, ref,
                                             source) == rounds


def test_pagerank_check_compares_a_neighbour_only_within_the_band(
        monkeypatch):
    from chipbench.jobs import pagerank

    traffic = {"l1": 1e-4, "stop_band": 2e-6, "damping": 0.85,
               "max_iters": 20, "limits": {"rank_l1_gap": 1e-5}}
    r = {k: np.full(4, float(k)) for k in (4, 5, 6)}

    def check(outputs, errs):
        monkeypatch.setattr(pagerank, "_reference", lambda *_: (5, r, errs))
        return pagerank.check({"n": 4, "m": 4}, outputs, traffic, 0)

    # the stop is clear of the tolerance: only iterate 5 is compared
    (_, gap, _), = check([r[4]], {4: 2e-4, 5: 5e-5, 6: 4e-5})[0]
    assert gap == 4.0
    # iterate 4's change lies within the band above it: 4 is admitted
    (_, gap, _), = check([r[4]], {4: 1.01e-4, 5: 5e-5, 6: 4e-5})[0]
    assert gap == 0.0
    # iterate 5's change lies within the band below it: 6 is admitted
    checks, failed, work = check([r[6], r[5]], {4: 2e-4, 5: 0.99e-4, 6: 8e-5})
    assert checks[0][1] == 0.0 and failed == 0
    assert work["least_bytes"] == (6 + 5) * (4 * 4 + 16 * 4)
