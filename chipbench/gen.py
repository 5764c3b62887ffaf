"""Graph500/GAP Kronecker graphs built on the device from a seed.

The edge list is drawn with ``jax.random`` (Graph500's Kronecker quadrant
rule, one bit of source and destination per level, then a random
relabelling of the vertices), weights are GAP's integers in [lo, hi] held
as float32, every drawn edge becomes two arcs with the same weight (GAP's
generated graphs, ``kron`` and ``urand``, are undirected), self-loops and
duplicate arcs are dropped (a duplicate keeps its least weight), and
vertices are renumbered by Degree-Based Grouping on the out-degree, as
``repro.core.reorder.dbg_order`` does it. The result is a
``repro.graph.csr.DeviceCSR`` with a fixed number of arc slots
(``2 * 2**scale * edge_factor``) so that every seed
compiles to the same programs: the arcs that dedup removed become padding
slots whose source and destination are ``n``, past the last vertex, which
the apps' gathers fill and their segment reductions drop.

Everything stays on the device but the edge count, which the host turns
into the seven DBG group bounds.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

DBG_GROUPS = 8  # repro.core.reorder.dbg_order's default


@partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c",
                                   "w_lo", "w_hi"))
def _edges(key, *, scale, edge_factor, a, b, c, w_lo, w_hi):
    """Deduplicated arcs (src, dst, w) in (dst, src) order, padding slots
    set to (n, n); plus the out-degree and the number of real arcs."""
    n = 1 << scale
    m = n * edge_factor
    k_bits, k_perm, k_w = jax.random.split(key, 3)
    ab = a + b
    # quadrants (src bit, dst bit): a=(0,0), b=(0,1), c=(1,0), d=(1,1)
    p_dst_one = jnp.array([b / ab, (1.0 - ab - c) / (1.0 - ab)], jnp.float32)

    def level(bit, carry):
        src, dst = carry
        u = jax.random.uniform(jax.random.fold_in(k_bits, bit), (2, m))
        s = u[0] >= ab
        d = u[1] < p_dst_one[s.astype(jnp.int32)]
        return (src | (s.astype(jnp.int32) << bit),
                dst | (d.astype(jnp.int32) << bit))

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    w = jax.random.randint(k_w, (m,), w_lo, w_hi + 1).astype(jnp.float32)
    src, dst = jnp.concatenate([src, dst]), jnp.concatenate([dst, src])
    w = jnp.concatenate([w, w])
    dst, src, w = jax.lax.sort((dst, src, w), num_keys=3)
    dup = jnp.concatenate([jnp.zeros((1,), bool),
                           (dst[1:] == dst[:-1]) & (src[1:] == src[:-1])])
    valid = (src != dst) & ~dup
    src = jnp.where(valid, src, n)
    dst = jnp.where(valid, dst, n)
    out_deg = jax.ops.segment_sum(valid.astype(jnp.int32), src,
                                  num_segments=n)
    return src, dst, w, out_deg, valid.sum()


def dbg_bounds(num_edges: int, num_nodes: int,
               num_groups: int = DBG_GROUPS) -> np.ndarray:
    """``bounds[g - 1]`` = how many degrees d in [0, n) fall in DBG group g
    or colder, for g = 1 .. num_groups - 1. Group of d is then
    ``sum(d < bounds)``. Computed with ``dbg_order``'s own float64 formula
    (average = edges / vertices), so the groups match it exactly."""
    d = np.arange(num_nodes, dtype=np.float64)
    avg = max(num_edges / num_nodes, 1e-9)
    with np.errstate(divide="ignore"):
        lvl = np.floor(np.log2(np.maximum(d / avg, 1e-9))).astype(np.int64)
    group = np.clip((num_groups - 2) - lvl, 0, num_groups - 1)
    # group is non-increasing in d
    return np.array([(group >= g).sum() for g in range(1, num_groups)],
                    np.int32)


@partial(jax.jit, static_argnames=("direction",))
def _relabel(src, dst, w, out_deg, bounds, *, direction):
    """DBG renumbering and the CSR of one direction.

    ``direction='in'``: edges sorted by (dst, src), ``indices`` = sources,
    ``dst`` = destinations, offsets over destinations (pull apps).
    ``direction='out'``: edges sorted by (src, dst), ``indices`` = targets,
    ``dst`` = sources, offsets over sources (push apps, as
    ``repro.graph.csr.transpose`` lays them out)."""
    n = out_deg.shape[0]
    group = (out_deg[:, None] < bounds[None, :]).sum(1)
    order = jnp.argsort(group, stable=True)
    rank = jnp.zeros((n + 1,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)).at[n].set(n)
    new_src, new_dst = rank[src], rank[dst]
    if direction == "in":
        row, col, w = jax.lax.sort((new_dst, new_src, w), num_keys=2)
    else:
        row, col, w = jax.lax.sort((new_src, new_dst, w), num_keys=2)
    counts = jax.ops.segment_sum(jnp.ones_like(row), row, num_segments=n)
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts, dtype=jnp.int32)])
    return indptr, col, row, w, rank[:n]


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``: the low 32 bits make the key and
    the high bits are folded in (with 64-bit types off a key holds 32)."""
    key = jax.random.key(seed % 2**32)
    return jax.random.fold_in(key, seed // 2**32 % 2**32)


def build(cfg: dict, seed: int, direction: str):
    """(DeviceCSR, number of real arcs, rank) for the configuration
    ``cfg`` (a ``chipbench/configs`` file) and ``seed``. ``rank[old] =
    new`` is the DBG renumbering of the drawn labels."""
    from repro.graph.csr import DeviceCSR

    if not cfg["symmetric"]:
        raise ValueError("the generator draws undirected graphs only, as "
                         "GAP's kron and urand are")
    n = 1 << cfg["scale"]
    key = seed_key(seed)
    src, dst, w, out_deg, m = _edges(
        key, scale=cfg["scale"], edge_factor=cfg["edge_factor"],
        a=cfg["a"], b=cfg["b"], c=cfg["c"],
        w_lo=cfg["weights"][0], w_hi=cfg["weights"][1])
    m = int(m)
    bounds = jnp.asarray(dbg_bounds(m, n))
    indptr, indices, rows, w, rank = _relabel(src, dst, w, out_deg, bounds,
                                              direction=direction)
    del src, dst, out_deg
    g = DeviceCSR(indptr=indptr, indices=indices, dst=rows, weights=w,
                  num_nodes=n)
    return g, m, rank
