"""Ligra-like vertex-centric engine (paper Sec. II-B, IV-A).

Pull-based: every active destination gathers its in-neighbours' properties
and reduces them. Push-based: every active source scatters its property to
its out-neighbours. Both are edge-parallel over the COO-ordered edge list —
the TPU-native formulation of the paper's CSR traversal, and the layer the
``hot_gather`` Pallas kernel plugs into.

The two reduce differently. Pull reduces into the rows of its own CSR, whose
arcs are sorted by destination, so each vertex's messages are one run of
slots: :func:`reduce_rows` combines each run with a segmented scan and reads
its last slot, dense passes and one read per vertex. Push reduces into the
targets, which are not sorted, and keeps the scatter
(``jax.ops.segment_min``/...). On TPU v5e the scatter is applied about one
slot at a time, 8.6 ns an arc slot at 2^26 slots (PageRank's reduction, 578
ms an iteration), which is why pull does without it.

Direction switching (Ligra's push/pull heuristic) selects pull when the
active frontier covers more than ``switch_fraction`` of edges.

The gather, the active-flag gather and the reduction run under the
``repro.obs`` scopes ``edge_map.gather``, ``edge_map.frontier`` and
``edge_map.reduce``, so a device trace reads each by name.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.graph.csr import DeviceCSR


def _zero(dtype):
    return jnp.zeros((), dtype)


def _top(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _bottom(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


@dataclasses.dataclass(frozen=True)
class Reducer:
    """An associative, commutative reduction of messages into vertices:
    ``op`` combines two messages, ``identity(dtype)`` is what a vertex with
    no message receives, and messages are cast to ``dtype`` first where it
    is set. Called as ``reducer(data, seg, n)`` it scatters into the
    unsorted segments ``seg`` with ``segment``, a ``jax.ops.segment_*``
    of the same reduction; :func:`reduce_rows` applies ``op`` along sorted
    rows instead."""

    op: Callable
    identity: Callable
    segment: Callable
    dtype: Optional[Any] = None

    def cast(self, data):
        return data if self.dtype is None else data.astype(self.dtype)

    def __call__(self, data, seg, n):
        return self.segment(self.cast(data), seg, num_segments=n)


sum_reduce = Reducer(jnp.add, _zero, jax.ops.segment_sum)
min_reduce = Reducer(jnp.minimum, _top, jax.ops.segment_min)
max_reduce = Reducer(jnp.maximum, _bottom, jax.ops.segment_max)
or_reduce = Reducer(jnp.maximum, _bottom, jax.ops.segment_max, jnp.uint32)

LANES = 128  # slots per row of the scan's [slots / 128, 128] layout


def _shift(a, k, axis, fill):
    """``a`` moved ``k`` places up ``axis``, the first ``k`` set to ``fill``."""
    cfg = [(0, 0, 0)] * a.ndim
    cfg[axis] = (k, -k, 0)
    return jax.lax.pad(a, jnp.asarray(fill, a.dtype), cfg)


def _segmented_scan(op, identity, x, starts, axis):
    """Inclusive scan of ``op`` along ``axis`` that restarts wherever
    ``starts`` is set (Hillis-Steele, log2 of the axis' length passes);
    returns the scan and whether a start lies at or before each place."""
    k = 1
    while k < x.shape[axis]:
        before = _shift(x, k, axis, identity)
        x = jnp.where(starts, x, op(before, x))
        starts = starts | _shift(starts, k, axis, False)
        k *= 2
    return x, starts


def reduce_rows(data, rows, indptr, reducer: Reducer = sum_reduce):
    """For each vertex v, ``reducer`` over ``data[indptr[v]:indptr[v+1]]``,
    or its identity where the row is empty. ``rows`` is the row of each
    slot, sorted (a CSR's ``dst``); slots past ``indptr[-1]`` (padding)
    reach no vertex.

    No scatter: the slots, laid out as [slots / 128, 128], are scanned
    along each lane row with ``reducer.op``, restarting where ``rows``
    changes; a second scan carries each lane row's end across lane rows,
    and a dense pass adds that carry to the slots before the first start in
    their lane row; vertex v then reads its last slot, ``indptr[v+1] - 1``.
    Only slots of one row are ever combined."""
    data = reducer.cast(data)
    identity = reducer.identity(data.dtype)
    m, rest = data.shape[0], data.shape[1:]
    r = max(1, -(-m // LANES))
    pad = r * LANES - m
    x = jnp.pad(data, [(0, pad)] + [(0, 0)] * len(rest),
                constant_values=identity).reshape((r, LANES) + rest)
    row = jnp.pad(rows, (0, pad))
    starts = (row != _shift(row, 1, 0, -1)).reshape(
        (r, LANES) + (1,) * len(rest))
    x, seen = _segmented_scan(reducer.op, identity, x, starts, axis=1)
    # the scan's value at the end of each lane row, carried across lane
    # rows, then shifted by one: what flows into each lane row from above
    ends, _ = _segmented_scan(reducer.op, identity, x[:, -1], seen[:, -1],
                              axis=0)
    carry = _shift(ends, 1, 0, identity)[:, None]
    x = jnp.where(seen, x, reducer.op(carry, x))

    first, end = indptr[:-1], indptr[1:]
    out = jnp.take(x.reshape((r * LANES,) + rest), jnp.maximum(end - 1, 0),
                   axis=0)
    return jnp.where((end > first).reshape((-1,) + (1,) * len(rest)), out,
                     identity)


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

    ``g`` is the in-edge CSR, whose arc slots are sorted by destination, so
    the reduction is :func:`reduce_rows` over its rows (``g.dst``,
    ``g.indptr``): a segmented scan, with no scatter over the arc slots,
    whatever ``reduce_fn``; on TPU v5e a scatter costs about 8.6 ns an arc
    slot, the scan's dense passes a small fraction of that.
    ``edge_map_push`` keeps the scatter: its targets are not sorted.
    """
    msgs = gather_src(g, prop, gather_impl)
    if edge_fn is not None:
        with jax.named_scope(obs.GATHER):
            msgs = edge_fn(msgs, g)
    if active_dst is not None:
        msgs = _mask(active_dst, g.dst, msgs, identity)
    with jax.named_scope(obs.REDUCE):
        return reduce_rows(msgs, g.dst, g.indptr, reduce_fn)


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
