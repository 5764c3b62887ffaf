"""Single-Source Shortest Path via Bellman-Ford (paper Table III: SSSP).

Push-based (the paper notes SSSP spends its ROI in push iterations): active
sources relax their out-edges; a vertex joins the next frontier when its
distance improved.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs
from repro.apps.engine import edge_map_push, frontier_arcs, min_reduce
from repro.graph.csr import DeviceCSR

INF = jnp.float32(jnp.inf)


@partial(jax.jit, static_argnames=("max_iters",))
def sssp_loop(
    g_out: DeviceCSR,
    source: int,
    max_iters: int = 10_000,
):
    """``(distances, stats)`` from ``source`` on the out-edge CSR ``g_out``
    (``g_out.dst`` = pushing source of each arc, ``g_out.indices`` = its
    target; see ``engine.edge_map_push``).

    ``stats["rounds"]`` counts the rounds run; two int32 arrays of
    ``max_iters`` entries hold one entry per round, 0 past the last:
    ``frontier_arcs`` (the out-arcs of the round's active sources, the
    useful relaxations) and ``arcs_relaxed`` (the arc slots the round's
    edge map reports it processed, the attempted ones)."""
    n = g_out.num_nodes
    w = g_out.weights if g_out.weights is not None else jnp.ones_like(
        g_out.indices, dtype=jnp.float32
    )

    def frontier(stats, r, active):
        # Counted on each round's result, which is the next round's
        # frontier (entry r + 1). Counted on the round's input, the extra
        # read of the flags moves them, on TPU v5e, out of the fast memory
        # that the flag gather reads them from.
        with jax.named_scope(obs.COUNTERS):
            return {**stats, "frontier_arcs": stats["frontier_arcs"].at[r].set(
                frontier_arcs(g_out, active), mode="drop")}

    def body(state):
        dist, active, it, stats = state
        best, slots = edge_map_push(g_out, dist, active_src=active,
                                    edge_fn=lambda msgs, _: msgs + w,
                                    reduce_fn=min_reduce, identity=INF)
        improved = best < dist
        with jax.named_scope(obs.COUNTERS):
            stats = {**stats,
                     "arcs_relaxed": stats["arcs_relaxed"].at[it].set(slots)}
        stats = frontier(stats, it + 1, improved)
        return jnp.minimum(dist, best), improved, it + 1, stats

    def cond(state):
        _, active, it, _ = state
        return active.any() & (it < max_iters)

    dist0 = jnp.full((n,), INF).at[source].set(0.0)
    active0 = jnp.zeros((n,), bool).at[source].set(True)
    with jax.named_scope(obs.COUNTERS):
        stats0 = {k: jnp.zeros((max_iters,), jnp.int32)
                  for k in ("frontier_arcs", "arcs_relaxed")}
    stats0 = frontier(stats0, 0, active0)
    dist, _, rounds, stats = jax.lax.while_loop(
        cond, body, (dist0, active0, 0, stats0))
    return dist, {"rounds": rounds, **stats}


def sssp(
    g_out: DeviceCSR,
    source: int,
    max_iters: int = 10_000,
) -> jnp.ndarray:
    """Distances from ``source`` (see :func:`sssp_loop`); the call's round
    and relaxation counts are kept in ``repro.obs`` under ``"sssp"``."""
    dist, stats = sssp_loop(g_out, source, max_iters=max_iters)
    obs.record("sssp", stats, sssp_loop, (g_out, source),
               max_iters=max_iters)
    return dist
