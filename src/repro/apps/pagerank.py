"""PageRank (paper Table III: PR) — iterative pull-based."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs
from repro.apps.engine import edge_map_pull, sum_reduce
from repro.graph.csr import DeviceCSR


@partial(jax.jit, static_argnames=("max_iters", "gather_impl"))
def pagerank_loop(
    g: DeviceCSR,
    damping: float = 0.85,
    tol: float = 1e-6,
    max_iters: int = 100,
    gather_impl: str = "jnp",
):
    """``(ranks, {"iterations": iterations run})``: the power iteration on
    the in-CSR ``g`` until the L1 change is at most ``tol`` per vertex."""
    n = g.num_nodes
    with jax.named_scope(obs.OUT_DEGREE):
        out_deg = jax.ops.segment_sum(
            jnp.ones_like(g.indices, dtype=jnp.float32), g.indices,
            num_segments=n)
    safe_deg = jnp.maximum(out_deg, 1.0)
    base = (1.0 - damping) / n

    def body(state):
        rank, _, it = state
        contrib = rank / safe_deg
        # dangling mass redistributed uniformly (matches networkx)
        dangling = jnp.sum(jnp.where(out_deg == 0, rank, 0.0))
        incoming = edge_map_pull(g, contrib, reduce_fn=sum_reduce,
                                 gather_impl=gather_impl)
        new_rank = base + damping * (incoming + dangling / n)
        err = jnp.sum(jnp.abs(new_rank - rank))
        return new_rank, err, it + 1

    def cond(state):
        _, err, it = state
        return (err > tol * n) & (it < max_iters)

    rank0 = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    rank, _, it = jax.lax.while_loop(cond, body, (rank0, jnp.inf, 0))
    return rank, {"iterations": it}


def pagerank(
    g: DeviceCSR,
    damping: float = 0.85,
    tol: float = 1e-6,
    max_iters: int = 100,
    gather_impl: str = "jnp",
) -> jnp.ndarray:
    """PageRank of the in-CSR ``g`` (see :func:`pagerank_loop`); the call's
    iteration count is kept in ``repro.obs`` under ``"pagerank"``."""
    rank, stats = pagerank_loop(g, damping, tol, max_iters=max_iters,
                                gather_impl=gather_impl)
    obs.record("pagerank", stats, pagerank_loop, (g, damping, tol),
               max_iters=max_iters, gather_impl=gather_impl)
    return rank
