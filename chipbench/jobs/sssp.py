"""SSSP queries (GAP's SSSP trial): ``repro.apps.sssp.sssp`` on the
weighted out-CSR, one query per job, sources drawn from the seed among the
vertices with out-degree > 0 as GAP picks them. Checked against float64
Dijkstra: the weights are integers, so the distances must be equal."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen, reference, work

DIRECTION = "out"


def _run(g, source):
    from repro.apps.sssp import sssp

    return sssp(g, source)


def setup(cfg, traffic, seed):
    g, m, _ = gen.build(cfg, seed, DIRECTION)
    out_deg = np.diff(np.asarray(g.indptr))
    rng = np.random.default_rng(seed)
    sources = rng.choice(np.flatnonzero(out_deg > 0),
                         size=traffic["sources"]).tolist()
    # the same program as every job; a source with no out-edge stops it
    # after one round
    jax.block_until_ready(_run(g, int(np.flatnonzero(out_deg == 0)[0])))
    return {"g": g, "m": m, "sources": sources, "traffic": traffic}


def run(state, i):
    sources = state["sources"]
    return jax.block_until_ready(_run(state["g"], sources[i % len(sources)]))


def host_graph(state):
    g, m = state["g"], state["m"]
    return {"n": g.num_nodes, "m": m, "indptr": np.asarray(g.indptr),
            "indices": np.asarray(g.indices[:m]),
            "weights": np.asarray(g.weights[:m]),
            "sources": state["sources"]}


def check(host, outputs, traffic, seed):
    """Every job's distances against Dijkstra from its source: the compared
    number is how many vertices differ, over all jobs (exact, limit 0).
    Also counts the Bellman-Ford rounds each query needs, which the
    per-round device time divides by."""
    n = host["n"]
    sources = [host["sources"][i % len(host["sources"])]
               for i in range(len(outputs))]
    ref = reference.dijkstra(host["indptr"], host["indices"],
                             host["weights"], n, sources)
    out_deg = np.diff(host["indptr"])
    limit = traffic["limits"]["dist_mismatch"]
    wrong, least, rounds = [], 0, 0
    for dist, r, s in zip(outputs, ref, sources):
        dist = np.asarray(dist, np.float64)
        wrong.append(int((dist != r).sum()))
        least += work.sssp_least_bytes(out_deg, np.isfinite(r))
        rounds += reference.bellman_ford_rounds(
            host["indptr"], host["indices"], host["weights"], r, s)
    failed = sum(w > limit for w in wrong)
    return ([("dist_mismatch", sum(wrong), limit)], failed,
            {"least_bytes": least, "rounds": rounds})


@partial(jax.jit, static_argnames=("dtype",))
def _sssp_lowp(g_out, source, *, dtype):
    """Frontier Bellman-Ford on the out-CSR with distances and weights in
    ``dtype``."""
    n = g_out.num_nodes
    inf = jnp.asarray(jnp.inf, dtype)
    w = g_out.weights.astype(dtype)

    def body(state):
        dist, active = state
        cand = jnp.where(jnp.take(active, g_out.dst),
                         jnp.take(dist, g_out.dst) + w, inf)
        best = jax.ops.segment_min(cand, g_out.indices, num_segments=n)
        return jnp.minimum(dist, best), best < dist

    def cond(state):
        return state[1].any()

    dist0 = jnp.full((n,), inf).at[source].set(0)
    active0 = jnp.zeros((n,), bool).at[source].set(True)
    dist, _ = jax.lax.while_loop(cond, body, (dist0, active0))
    return dist


def control(state, traffic, dtype):
    """One query of the plain reference put in the program's place, with
    distances and weights in ``dtype``."""
    return jax.block_until_ready(
        _sssp_lowp(state["g"], state["sources"][0], dtype=dtype))

