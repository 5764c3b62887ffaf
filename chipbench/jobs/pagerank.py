"""PageRank jobs (GAP's PR trial): ``repro.apps.pagerank.pagerank`` on the
in-CSR, damping and stop rule from the traffic file, every job on the same
graph. Checked against the float64 power iteration."""
from __future__ import annotations

import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen, reference, work

DIRECTION = "in"


def _run(g, traffic, tol, max_iters=None):
    from repro.apps.pagerank import pagerank

    return pagerank(g, damping=traffic["damping"], tol=tol,
                    max_iters=(traffic["max_iters"] if max_iters is None
                               else max_iters))


def setup(cfg, traffic, seed):
    g, m, _ = gen.build(cfg, seed, DIRECTION)
    # the same program as every job; an infinite tolerance stops it before
    # its first iteration
    jax.block_until_ready(_run(g, traffic, float("inf")))
    return {"g": g, "m": m, "traffic": traffic}


def run(state, i):
    g, traffic = state["g"], state["traffic"]
    return jax.block_until_ready(
        _run(g, traffic, traffic["l1"] / g.num_nodes))


def host_graph(state):
    g, m = state["g"], state["m"]
    return {"n": g.num_nodes, "m": m, "indptr": np.asarray(g.indptr),
            "indices": np.asarray(g.indices[:m])}


def _reference(host, traffic):
    return reference.pagerank_iterates(
        host["indptr"], host["indices"], host["n"], traffic["damping"],
        traffic["l1"], traffic["max_iters"])


def check(host, outputs, traffic, seed):
    """Each job's ranks against the float64 stopping iterate K. The same
    rule in float32 may stop one iteration either side only where the
    float64 L1 change at that iteration lies within ``stop_band`` of the
    tolerance; only then is that neighbour compared too. The compared
    number is the worst job's L1 gap."""
    iters, ref, errs = _reference(host, traffic)
    l1, band = traffic["l1"], traffic["stop_band"]
    admitted = [iters]
    if iters - 1 in errs and errs[iters - 1] <= l1 + band:
        admitted.append(iters - 1)
    if iters + 1 in ref and errs[iters] > l1 - band:
        admitted.append(iters + 1)
    limit = traffic["limits"]["rank_l1_gap"]
    gaps, matched = [], []
    for rank in outputs:
        rank = np.asarray(rank, np.float64)
        gap, k = min((float(np.abs(rank - ref[k]).sum()), k)
                     for k in admitted)
        gaps.append(gap)
        matched.append(k)
    worst = max(gaps)
    failed = sum(not (g <= limit) for g in gaps)
    print(f"[check] float64 reference stops at iteration {iters} (GAP rule "
          f"L1 <= {l1}; L1 change there {errs[iters]!r}); iterates "
          f"compared {sorted(admitted)}; jobs matched iterates {matched}",
          file=sys.stderr)
    least = sum(work.pagerank_least_bytes(host["n"], host["m"], k)
                for k in matched)
    return ([("rank_l1_gap", worst, limit)], failed,
            {"least_bytes": least, "reference_iterations": iters})


@partial(jax.jit, static_argnames=("max_iters", "dtype"))
def _pagerank_lowp(g, damping, l1, *, max_iters, dtype):
    """The reference's power iteration with ranks, contributions and sums
    held in ``dtype``; same stop rule."""
    n = g.num_nodes
    out_deg = jax.ops.segment_sum(jnp.ones(g.indices.shape, dtype), g.indices,
                                  num_segments=n)
    inv_deg = (1 / jnp.maximum(out_deg, 1)).astype(dtype)
    dangling = out_deg == 0
    base = jnp.asarray((1.0 - damping) / n, dtype)

    def body(state):
        rank, _, it = state
        contrib = rank * inv_deg
        incoming = jax.ops.segment_sum(jnp.take(contrib, g.indices), g.dst,
                                       num_segments=n)
        lost = jnp.sum(jnp.where(dangling, rank, 0)).astype(dtype)
        new = base + jnp.asarray(damping, dtype) * (incoming + lost / n)
        err = jnp.abs(new.astype(jnp.float32) - rank.astype(jnp.float32)).sum()
        return new.astype(dtype), err, it + 1

    def cond(state):
        _, err, it = state
        return (err > l1) & (it < max_iters)

    rank0 = jnp.full((n,), 1.0 / n, dtype)
    rank, _, _ = jax.lax.while_loop(cond, body,
                                    (rank0, jnp.float32(jnp.inf), 0))
    return rank


def control(state, traffic, dtype):
    """One job of the plain reference put in the program's place, in
    ``dtype``."""
    return jax.block_until_ready(_pagerank_lowp(
        state["g"], traffic["damping"], traffic["l1"],
        max_iters=traffic["max_iters"], dtype=dtype))


def faults(state, traffic, host):
    """The program run wrong on purpose: one job stopped one iteration
    before the float64 reference's stop."""
    iters, _, _ = _reference(host, traffic)
    g = state["g"]
    return {"one_iteration_short": jax.block_until_ready(
        _run(g, traffic, traffic["l1"] / g.num_nodes, max_iters=iters - 1))}
