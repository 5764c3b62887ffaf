"""Graph applications vs independent references (networkx / hand Brandes)."""
import collections

import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro import apps
from repro.graph import generate
from repro.graph.csr import transpose
from repro.graph.generate import add_uniform_weights


@pytest.fixture(scope="module")
def g():
    return generate.rmat(9, 8, seed=3)


@pytest.fixture(scope="module")
def nxg(g):
    G = nx.DiGraph()
    G.add_nodes_from(range(g.num_nodes))
    G.add_edges_from(zip(g.indices.tolist(), g.dst_ids().tolist()))
    return G


def test_pagerank_matches_networkx(g, nxg):
    pr = np.asarray(apps.pagerank(g.device(), tol=1e-9, max_iters=200))
    ref = nx.pagerank(nxg, alpha=0.85, tol=1e-10)
    ref = np.array([ref[i] for i in range(g.num_nodes)])
    assert pr.sum() == pytest.approx(1.0, abs=1e-3)
    assert np.abs(pr - ref).max() < 1e-4


def test_pagerank_delta_approximates_pagerank(g):
    pr = np.asarray(apps.pagerank(g.device(), tol=1e-9, max_iters=200))
    prd = np.asarray(apps.pagerank_delta(g.device(), epsilon=1e-9, max_iters=300))
    # PRD is an approximation (no dangling redistribution): rankings agree
    k = 50
    top_pr = set(np.argsort(-pr)[:k].tolist())
    top_prd = set(np.argsort(-prd)[:k].tolist())
    assert len(top_pr & top_prd) >= int(0.8 * k)


def test_sssp_matches_dijkstra(g):
    gw = add_uniform_weights(g, seed=1)
    gout = transpose(gw)
    dist = np.asarray(apps.sssp(gout.device(), 0))
    GW = nx.DiGraph()
    GW.add_nodes_from(range(g.num_nodes))
    for s, d, w in zip(gw.indices.tolist(), gw.dst_ids().tolist(),
                       gw.weights.tolist()):
        GW.add_edge(s, d, weight=w)
    ref = nx.single_source_dijkstra_path_length(GW, 0)
    for v, rd in ref.items():
        assert dist[v] == pytest.approx(rd, abs=1e-3)
    for v in range(g.num_nodes):
        if v not in ref:
            assert np.isinf(dist[v])


def _brandes_ref(G, s):
    S, P = [], collections.defaultdict(list)
    sigma = collections.defaultdict(float)
    dist = {s: 0}
    sigma[s] = 1.0
    Q = collections.deque([s])
    while Q:
        v = Q.popleft()
        S.append(v)
        for w in G.successors(v):
            if w not in dist:
                dist[w] = dist[v] + 1
                Q.append(w)
            if dist[w] == dist[v] + 1:
                sigma[w] += sigma[v]
                P[w].append(v)
    delta = collections.defaultdict(float)
    while S:
        w = S.pop()
        for v in P[w]:
            delta[v] += sigma[v] / sigma[w] * (1 + delta[w])
    return delta, sigma, dist


def test_bc_matches_brandes(g, nxg):
    delta, sigma, level = apps.bc_single_source(transpose(g).device(), 0)
    delta, sigma, level = map(np.asarray, (delta, sigma, level))
    dref, sgref, distref = _brandes_ref(nxg, 0)
    for v, d in distref.items():
        assert level[v] == d
        assert sigma[v] == pytest.approx(sgref[v], rel=1e-4)
    for v, dd in dref.items():
        assert delta[v] == pytest.approx(dd, rel=1e-2, abs=1e-2)


def test_radii_lower_bounds_eccentricity(g, nxg):
    roots = jnp.arange(8, dtype=jnp.int32)
    radii, mask = apps.radii_estimate(g.device(), roots)
    radii = np.asarray(radii)
    # radii estimates are bounded by the largest BFS depth from any root
    assert radii.min() >= 0
    und = nxg.reverse()  # pull over in-edges = forward BFS on reversed graph
    for r in range(8):
        lengths = nx.single_source_shortest_path_length(und, r)
        max_depth = max(lengths.values())
        assert radii.max() <= max_depth + 8  # loose sanity bound


def test_engine_pull_push_consistency(g):
    """Pull over in-CSR == push over out-CSR for a linear reduction."""
    from repro.apps.engine import edge_map_pull, edge_map_push, sum_reduce

    prop = jnp.asarray(np.random.default_rng(0).random(g.num_nodes),
                       dtype=jnp.float32)
    pull = edge_map_pull(g.device(), prop, reduce_fn=sum_reduce)
    push, _ = edge_map_push(
        transpose(g).device(), prop, reduce_fn=sum_reduce, identity=0.0
    )
    assert np.allclose(np.asarray(pull), np.asarray(push), atol=1e-3)
