"""The apps' own counts and scopes (``repro.obs``): PageRank's iterations
and SSSP's rounds and frontiers against float64 numpy loops, the public
entries against the loops they call, and the scope map of a CPU compile."""
import math
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.apps.pagerank import pagerank, pagerank_loop
from repro.apps.sssp import sssp, sssp_loop
from repro.graph import generate
from repro.graph.csr import CSR, DeviceCSR, transpose

DAMPING, L1 = 0.85, 1e-4
# float32 rounding of the L1 change over a thousand vertices lies far
# below this; only a float64 change this close to the tolerance could stop
# the float32 loop one iteration either side
STOP_BAND = 1e-6


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.clear()
    yield
    obs.clear()


def _power_iteration(g: CSR, l1: float, max_iters: int = 100):
    """float64 PageRank as ``pagerank_loop`` defines it (dangling mass
    spread uniformly); the L1 change of each iteration until the first at
    or under ``l1``."""
    n, src, dst = g.num_nodes, g.indices, g.dst_ids()
    out_deg = np.bincount(src, minlength=n).astype(np.float64)
    rank, errs = np.full(n, 1.0 / n), []
    while len(errs) < max_iters:
        incoming = np.bincount(dst, weights=(rank / np.maximum(out_deg, 1))
                               [src], minlength=n)
        new = (1 - DAMPING) / n + DAMPING * (incoming
                                             + rank[out_deg == 0].sum() / n)
        errs.append(np.abs(new - rank).sum())
        rank = new
        if errs[-1] <= l1:
            break
    return errs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pagerank_counts_the_float64_stopping_iteration(seed):
    g = generate.rmat(10, 16, seed=seed)
    errs = _power_iteration(g, L1)
    pagerank(g.device(), DAMPING, L1 / g.num_nodes, max_iters=100)
    (call,) = obs.calls("pagerank")
    iterations = int(obs.counts(call)["iterations"])
    if all(abs(e - L1) > STOP_BAND for e in errs[-2:]):
        assert iterations == len(errs)
    else:
        assert abs(iterations - len(errs)) <= 1


def _weighted_out_csr(seed: int, pad: int) -> tuple:
    """A scale-10 Kronecker out-CSR with integer weights (exact path sums
    in float32) and ``pad`` padding slots past the last vertex, as the
    benchmark's graphs have; and its arcs on the host."""
    g = generate.rmat(10, 16, seed=seed)
    w = np.random.default_rng(seed).integers(1, 256, g.num_edges)
    g_out = transpose(CSR(g.indptr, g.indices, g.num_nodes,
                          w.astype(np.float32)))
    n = g_out.num_nodes
    d = g_out.device()
    fill = lambda a, v: jnp.concatenate([a, jnp.full((pad,), v, a.dtype)])  # noqa: E731
    dev = DeviceCSR(indptr=d.indptr, indices=fill(d.indices, n),
                    dst=fill(d.dst, n), weights=fill(d.weights, 1.0),
                    num_nodes=n)
    return dev, g_out


def _frontier_bellman_ford(g_out: CSR, source: int):
    """Distances and, per round, the out-arcs of the active sources of the
    synchronous frontier Bellman-Ford that ``sssp_loop`` runs, in float64
    numpy."""
    n = g_out.num_nodes
    row = g_out.dst_ids()
    out_deg = np.diff(g_out.indptr)
    dist = np.full(n, np.inf)
    dist[source] = 0
    active = np.zeros(n, bool)
    active[source] = True
    arcs = []
    while active.any():
        arcs.append(int(out_deg[active].sum()))
        cand = np.where(active[row], dist[row] + g_out.weights, np.inf)
        best = np.full(n, np.inf)
        np.minimum.at(best, g_out.indices, cand)
        active = best < dist
        dist = np.minimum(dist, best)
    return dist, arcs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sssp_counts_match_the_frontier_bellman_ford(seed):
    pad = 37
    dev, g_out = _weighted_out_csr(seed, pad)
    source = int(np.argmax(np.diff(g_out.indptr) > 0))
    dist = np.asarray(sssp(dev, source, max_iters=64))
    ref, arcs = _frontier_bellman_ford(g_out, source)
    np.testing.assert_array_equal(dist, ref)
    c = obs.counts(obs.calls("sssp")[-1])
    rounds = len(arcs)
    assert int(c["rounds"]) == rounds
    assert c["frontier_arcs"][:rounds].tolist() == arcs
    # every round's edge map processes every slot, padding included
    assert c["arcs_relaxed"][:rounds].tolist() == [g_out.num_edges + pad] \
        * rounds
    for k in ("frontier_arcs", "arcs_relaxed"):
        assert not c[k][rounds:].any()


def test_sssp_counts_the_slots_its_edge_map_reports(monkeypatch):
    """``arcs_relaxed`` is what the round's edge map says it processed, not
    the arc array's length: an edge map that did less reads less."""
    # ``repro.apps.sssp`` names the function; the module is in sys.modules
    sssp_mod = sys.modules[sssp_loop.__module__]
    push = sssp_mod.edge_map_push

    def fewer(*args, **kwargs):
        out, slots = push(*args, **kwargs)
        return out, slots // 3

    monkeypatch.setattr(sssp_mod, "edge_map_push", fewer)
    dev, _ = _weighted_out_csr(1, 0)
    loop = jax.jit(sssp_loop.__wrapped__, static_argnames=("max_iters",))
    _, stats = loop(dev, 0, max_iters=64)
    rounds = int(stats["rounds"])
    assert rounds > 1
    assert np.asarray(stats["arcs_relaxed"])[:rounds].tolist() == \
        [dev.dst.shape[0] // 3] * rounds


def test_public_entries_return_what_their_loops_return():
    g = generate.rmat(9, 8, seed=3)
    dev = g.device()
    rank, stats = pagerank_loop(dev, DAMPING, 1e-9, max_iters=50)
    got = pagerank(dev, DAMPING, 1e-9, max_iters=50)
    assert np.array_equal(np.asarray(got), np.asarray(rank))
    (call,) = obs.calls("pagerank")
    assert int(obs.counts(call)["iterations"]) == int(stats["iterations"])

    dev, _ = _weighted_out_csr(4, 0)
    dist, stats = sssp_loop(dev, 5, max_iters=64)
    got = sssp(dev, 5, max_iters=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dist))
    (call,) = obs.calls("sssp")
    for k, v in obs.counts(call).items():
        np.testing.assert_array_equal(v, np.asarray(stats[k]))


def test_registry_is_bounded_and_keeps_no_traced_call():
    dev = generate.rmat(6, 4, seed=1).device()
    for _ in range(obs.KEEP + 3):
        pagerank(dev, max_iters=2)
    assert len(obs.calls("pagerank")) == obs.KEEP
    assert len({c.key for c in obs.calls("pagerank")}) == 1
    jax.jit(lambda d: pagerank(d, max_iters=2))(dev)
    assert len(obs.calls("pagerank")) == obs.KEEP


def arc_sized_fusions(text: str, m: int) -> list:
    """Fusion instructions whose result or fused parameters hold ``m``
    elements, in any shape (``[m]``, ``[m,1]``, ``[m/128,128]``)."""
    params, fusions = {}, []

    def holds_m(shapes):
        return any(math.prod(int(d) for d in dims.split(",")) == m
                   for dims in re.findall(r"\[(\d+(?:,\d+)*)\]", shapes))

    for line in text.splitlines():
        head = re.match(r"^%?([\w.\-]+) \((.*)\) -> ", line)
        if head:
            params[head.group(1)] = head.group(2)
            continue
        f = re.match(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.+?) fusion\(.*"
                     r"calls=%?([\w.\-]+)", line)
        if f:
            fusions.append((f.group(1), f.group(2), f.group(3)))
    return [name for name, shape, called in fusions
            if holds_m(shape) or holds_m(params.get(called, ""))]


def test_scope_map_gives_every_arc_sized_fusion_a_scope():
    """On the CPU, whose fusion pass merges more than the TPU's (the
    distance gather into the mask's select, for one): every fusion over the
    arcs is under one of the app's scopes, the reduction apart from the
    rest. The TPU's own split is checked in ``test_chip_compile.py``."""
    g = generate.rmat(10, 16, seed=0)
    m = g.num_edges
    pagerank(g.device(), max_iters=3)
    dev, _ = _weighted_out_csr(0, 0)
    sssp(dev, 0, max_iters=64)
    allowed = {"pagerank": {obs.GATHER, obs.REDUCE, obs.OUT_DEGREE},
               "sssp": {obs.GATHER, obs.FRONTIER, obs.REDUCE}}
    for app, scopes in allowed.items():
        call = obs.calls(app)[-1]
        scope_of = obs.scope_map(call)
        found = {scope_of[f] for f in arc_sized_fusions(obs.hlo(call), m)}
        assert obs.REDUCE in found and found <= scopes, (app, found)
        assert obs.scope_map(call) is scope_of  # kept per program
