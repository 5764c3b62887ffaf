"""What the graph apps let a reader see of their device work.

Two things, and the only place that names either:

- **Scopes.** The edge map and the apps wrap their device work in
  ``jax.named_scope`` under the names below. A scope changes only the HLO
  metadata (``op_name``), not the compiled instructions, so with tracing off
  it costs nothing. :func:`scope_map` maps each instruction of a call's
  compiled program to its innermost scope, which is how a profiler trace,
  whose device ops carry XLA's instruction names, is read by scope.
- **Counters.** Each app's loop returns, beside its result, counts it kept
  on the device (iterations, rounds, relaxations). The apps' public entry
  points append one :class:`Call` per call to a bounded registry, holding
  the counters as device arrays, never fetched in the call; :func:`counts`
  fetches them when a reader asks.

Recording costs a list append and the arguments' abstract shapes: no device
sync and no lowering on the call path.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Callable, Optional

import jax
import numpy as np

GATHER = "edge_map.gather"        # the property gather, prop[src]
FRONTIER = "edge_map.frontier"    # the gather of the active flags and its mask
REDUCE = "edge_map.reduce"        # the segment reduction into the vertices
OUT_DEGREE = "pagerank.out_degree"
COUNTERS = "obs.counters"         # the counters' own device work
SCOPES = (GATHER, FRONTIER, REDUCE, OUT_DEGREE, COUNTERS)

# Calls kept per app. A PageRank call holds one int32 scalar; an SSSP call
# two int32 arrays of ``max_iters`` entries and a scalar, so 80,004 bytes
# at its default of 10,000 rounds: at most 5.2 MB of device memory for the
# SSSP calls kept.
KEEP = 64


@dataclasses.dataclass(frozen=True)
class Call:
    """One call of an app's loop: its counters, still on the device, and what
    it takes to compile the same program again (the jitted loop, the
    arguments' abstract shapes, the static arguments)."""

    app: str
    stats: dict
    program: Callable
    args: tuple
    static: tuple

    @property
    def key(self):
        """Which compiled program the call ran: equal keys, one program."""
        leaves, tree = jax.tree.flatten(self.args)
        return (self.app, tree, tuple(leaves), self.static)


_calls: dict = collections.defaultdict(
    lambda: collections.deque(maxlen=KEEP))
_maps: dict = {}


def _spec(x):
    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                weak_type=aval.weak_type,
                                sharding=getattr(x, "sharding", None))


def record(app: str, stats: dict, program: Callable, args: tuple,
           **static) -> None:
    """Keep one call of ``app``'s jitted ``program`` on ``args`` (its
    non-static arguments, in order) with ``static`` keyword arguments. A
    call made while tracing (inside another ``jit``) has no counts of its
    own and is not kept."""
    if any(isinstance(v, jax.core.Tracer) for v in jax.tree.leaves(stats)):
        return
    _calls[app].append(Call(app, stats, program, jax.tree.map(_spec, args),
                            tuple(sorted(static.items()))))


def calls(app: str) -> list:
    """The calls of ``app`` kept, oldest first."""
    return list(_calls[app])


def clear() -> None:
    _calls.clear()
    _maps.clear()


def counts(call: Call) -> dict:
    """One call's counters on the host as int64: a scalar per loop count,
    an array per per-round counter, entries past the last round 0. Sums over
    rounds are to be taken on these, not on the device's int32."""
    return {k: np.asarray(v).astype(np.int64)
            for k, v in jax.device_get(call.stats).items()}


def hlo(call: Call) -> str:
    """The compiled HLO text of the program ``call`` ran, compiled again
    from its recorded shapes (where a persistent compile cache holds it,
    read from there)."""
    return call.program.lower(*call.args, **dict(call.static)) \
        .compile().as_text()


def scope_map(call: Call) -> dict:
    """{HLO instruction name: innermost scope of :data:`SCOPES`, or None}
    for the program ``call`` ran (:func:`scopes_of_hlo` of :func:`hlo`),
    kept per program."""
    key = call.key
    if key not in _maps:
        _maps[key] = scopes_of_hlo(hlo(call))
    return _maps[key]


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def _innermost(op_name: Optional[str]) -> Optional[str]:
    """The innermost of :data:`SCOPES` in an ``op_name`` path, or None."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return None


def scopes_of_hlo(text: str) -> dict:
    """{instruction name: scope or None} for compiled HLO text
    (``Compiled.as_text()``). An instruction takes the innermost scope of
    its ``op_name``; a fusion takes its root instruction's, nested fusions
    followed down, and its own where the root has none (a tuple)."""
    own, called, root = {}, {}, {}
    computation = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = _innermost(op.group(1) if op else None)
        c = _CALLS.search(line)
        if c and " fusion(" in line:
            called[name] = c.group(1)
        if m.group(1):
            root[computation] = name

    def scope(name):
        sub = called.get(name)
        inner = scope(root[sub]) if sub in root else None
        return inner if inner is not None else own.get(name)

    return {name: scope(name) for name in own}
