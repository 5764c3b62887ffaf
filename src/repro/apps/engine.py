"""Ligra-like vertex-centric engine (paper Sec. II-B, IV-A).

Pull-based: every active destination gathers its in-neighbours' properties
and reduces them. Push-based: every active source scatters its property to
its out-neighbours. Both are expressed as edge-parallel segment reductions
(`jax.ops.segment_sum`/`segment_min`/...) over the COO-ordered edge list —
the TPU-native formulation of the paper's CSR traversal, and the layer the
``hot_gather`` Pallas kernel plugs into.

Direction switching (Ligra's push/pull heuristic) selects pull when the
active frontier covers more than ``switch_fraction`` of edges.

The gather, the active-flag gather and the reduction run under the
``repro.obs`` scopes ``edge_map.gather``, ``edge_map.frontier`` and
``edge_map.reduce``, so a device trace reads each by name.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.graph.csr import DeviceCSR

Reducer = Callable[[jnp.ndarray, jnp.ndarray, int], jnp.ndarray]


def sum_reduce(data, seg, n):
    return jax.ops.segment_sum(data, seg, num_segments=n)


def min_reduce(data, seg, n):
    return jax.ops.segment_min(data, seg, num_segments=n)


def max_reduce(data, seg, n):
    return jax.ops.segment_max(data, seg, num_segments=n)


def or_reduce(data, seg, n):
    return jax.ops.segment_max(data.astype(jnp.uint32), seg, num_segments=n)


def gather_src(g: DeviceCSR, prop: jnp.ndarray, gather_impl: str = "jnp") -> jnp.ndarray:
    """prop[src] for every edge — THE hot path the paper targets.

    ``gather_impl='pallas_hot'`` routes through the two-tier VMEM-pinned
    kernel (``repro.kernels.hot_gather``); 'jnp' is the reference path used
    on CPU and inside the distributed step.
    """
    with jax.named_scope(obs.GATHER):
        if gather_impl == "jnp":
            return jnp.take(prop, g.indices, axis=0)
        if gather_impl == "pallas_hot":
            from repro.kernels.hot_gather import ops as hot_ops

            return hot_ops.hot_gather(prop, g.indices)
    raise ValueError(gather_impl)


def _mask(active, idx, msgs, identity):
    """Messages whose edge's vertex ``idx`` is inactive become ``identity``."""
    with jax.named_scope(obs.FRONTIER):
        mask = jnp.take(active, idx)
        shape = (-1,) + (1,) * (msgs.ndim - 1)
        return jnp.where(mask.reshape(shape), msgs, identity)


def edge_map_pull(
    g: DeviceCSR,
    prop: jnp.ndarray,
    active_dst: Optional[jnp.ndarray] = None,
    edge_fn: Optional[Callable] = None,
    reduce_fn: Reducer = sum_reduce,
    identity: float = 0.0,
    gather_impl: str = "jnp",
) -> jnp.ndarray:
    """For each vertex v: reduce(edge_fn(prop[src]) for src in in_nbrs(v)).

    ``active_dst`` masks destinations (inactive vertices receive
    ``identity``). Messages into inactive vertices are replaced by the
    identity before the reduction, matching Ligra's edgeMap semantics.
    """
    msgs = gather_src(g, prop, gather_impl)
    if edge_fn is not None:
        with jax.named_scope(obs.GATHER):
            msgs = edge_fn(msgs, g)
    if active_dst is not None:
        msgs = _mask(active_dst, g.dst, msgs, identity)
    with jax.named_scope(obs.REDUCE):
        return reduce_fn(msgs, g.dst, g.num_nodes)


def edge_map_push(
    g: DeviceCSR,
    prop: jnp.ndarray,
    active_src: Optional[jnp.ndarray] = None,
    edge_fn: Optional[Callable] = None,
    reduce_fn: Reducer = min_reduce,
    identity: float = jnp.inf,
    gather_impl: str = "jnp",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(out, slots)``: for each vertex v, ``out[v]`` = reduce(edge_fn(prop[u])
    for u with an arc u -> v), pushed along out-arcs; ``slots`` the arc slots
    this call processed (int32), i.e. the relaxations it attempted.

    ``g`` is the out-edge CSR (``transpose`` of the in-edge one): ``g.dst``
    holds the source u of each out-arc and ``g.indices`` its target v.
    ``active_src`` masks sources: an inactive source's messages are
    replaced by ``identity`` before the reduction, so every slot, padding
    included, is processed whatever the frontier."""
    with jax.named_scope(obs.GATHER):
        msgs = jnp.take(prop, g.dst, axis=0)
        if edge_fn is not None:
            msgs = edge_fn(msgs, g)
    if active_src is not None:
        msgs = _mask(active_src, g.dst, msgs, identity)
    with jax.named_scope(obs.REDUCE):
        out = reduce_fn(msgs, g.indices, g.num_nodes)
    return out, jnp.int32(g.dst.shape[0])


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    switch_fraction: float = 0.05  # Ligra's |frontier edges| / |E| threshold
    gather_impl: str = "jnp"


def choose_direction(g: DeviceCSR, active: jnp.ndarray, cfg: EngineConfig) -> jnp.ndarray:
    """True -> pull (dense frontier), False -> push (sparse frontier)."""
    return frontier_arcs(g, active) > cfg.switch_fraction * g.indices.shape[0]


def frontier_arcs(g: DeviceCSR, active: jnp.ndarray) -> jnp.ndarray:
    """The arcs of the ``active`` vertices' CSR rows (for the out-edge CSR,
    their out-arcs), as int32."""
    return jnp.sum(jnp.where(active, jnp.diff(g.indptr), 0), dtype=jnp.int32)
