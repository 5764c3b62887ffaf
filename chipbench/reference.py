"""Plain float64 references for the graph cells, on the host.

They share no code with ``repro``: scipy power iteration for PageRank,
scipy's Dijkstra for SSSP and the Bellman-Ford round count that follows
from its distances, over the host copy of the graph the harness built (the
input, not anything the apps made).
"""
from __future__ import annotations

import numpy as np


def pagerank_iterates(indptr, indices, n, damping, l1, max_iters):
    """Float64 PageRank on the in-CSR, dangling mass spread uniformly, as
    ``repro.apps.pagerank`` defines it. Iterates until ``||r_k -
    r_{k-1}||_1 <= l1`` or ``max_iters``; returns ``(K, {k: r_k}, {k:
    ||r_k - r_{k-1}||_1})`` for the stopping iterate K and its neighbours
    K-1 and K+1 (K+1 only when the stop came from the L1 rule), so that a
    check can tell whether the same rule in float32 could stop one
    iteration either side."""
    import scipy.sparse as sp

    a = sp.csr_matrix((np.ones(indices.size), indices, indptr), shape=(n, n))
    out_deg = np.bincount(indices, minlength=n).astype(np.float64)
    dangling = out_deg == 0
    inv_deg = 1.0 / np.maximum(out_deg, 1.0)
    rank = np.full(n, 1.0 / n)
    kept, errs = {0: rank}, {}
    stop = None
    for it in range(1, max_iters + 1):
        rank = (1.0 - damping) / n + damping * (
            a @ (rank * inv_deg) + rank[dangling].sum() / n)
        errs[it] = float(np.abs(rank - kept[it - 1]).sum())
        kept[it] = rank
        kept.pop(it - 3, None)
        if stop is not None:   # this was K + 1
            break
        if errs[it] <= l1:
            stop = it
    if stop is None:
        stop = max_iters
    near = [k for k in (stop - 1, stop, stop + 1) if k in kept]
    return stop, {k: kept[k] for k in near}, {k: errs[k] for k in near
                                               if k in errs}


def bellman_ford_rounds(indptr, indices, weights, dist, source):
    """Rounds a synchronous frontier Bellman-Ford takes from ``source`` on
    the weighted out-CSR, given the exact distances ``dist``: after round k
    every distance is the shortest over paths of at most k arcs, so the
    loop changes something for h rounds, where h is the most arcs any
    reached vertex needs on a shortest path, and stops after round h + 1,
    which changes nothing. h is found on the arcs that lie on shortest
    paths (weights are positive, so they form a DAG)."""
    n = dist.size
    row = np.repeat(np.arange(n), np.diff(indptr))
    tight = np.isfinite(dist[row]) & (dist[row] + weights == dist[indices])
    u, v = row[tight], indices[tight]
    order = np.argsort(v, kind="stable")
    u, v = u[order], v[order]
    heads, starts = np.unique(v, return_index=True)
    hops = np.full(n, np.inf)
    hops[source] = 0
    h = 0
    while True:
        best = np.minimum.reduceat(hops[u] + 1, starts) if u.size else \
            np.zeros(0)
        new = np.minimum(hops[heads], best)
        if (new == hops[heads]).all():
            return h + 1
        hops[heads] = new
        h += 1


def dijkstra(indptr, indices, weights, n, sources):
    """Float64 Dijkstra distances, one row per source, on the weighted
    out-CSR (row = edge source)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra as sp_dijkstra

    m = sp.csr_matrix((weights.astype(np.float64), indices, indptr),
                      shape=(n, n))
    return sp_dijkstra(m, directed=True, indices=np.asarray(sources))
