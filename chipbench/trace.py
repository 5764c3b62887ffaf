"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The harness wraps its measured window in a host span named
``bench:window`` and each job in ``bench:job``; JAX's profiler records
these beside the device planes, on the same clock. From one trace this
module gives, for the window:

- ``busy_s`` per device: the union of the intervals in which an operation
  ran (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane);
- ``window_s``: the length of the ``bench:window`` span;
- per-op self time (nested events counted once);
- exposed collective time per device: collective intervals during which no
  other operation ran on that device;
- the longest idle gaps, each labelled with the innermost ``bench:`` host
  span that covers its midpoint (``host`` where none does).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"allgather|allreduce|reducescatter", re.IGNORECASE)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: list            # per device, seconds
    op_self_s: dict         # op name -> self seconds, summed over devices
    exposed_collective_s: list  # per device
    idle_gaps: list         # [(label, seconds)], longest first

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0

    @property
    def idle_pct(self) -> float:
        """Share (%) of the window in which no op ran, averaged over the
        devices."""
        return 100.0 * (1.0 - self.mean_busy_s / self.window_s)


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` file the profiler wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def union(intervals):
    """Sorted, merged copy of ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """{name: self time} for possibly nested ``[(name, start, end)]`` on
    one line: a child's time is taken from its parent's."""
    totals = {}
    stack = []  # [name, start, end, child time]

    def close(item):
        name, s, e, child = item
        totals[name] = totals.get(name, 0.0) + (e - s) - child

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return totals


def _events(line):
    return [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
            for ev in line.events]


def host_spans(profile):
    """``[(name, start_s, end_s)]`` of the harness's ``bench:`` spans."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[0].startswith(SPAN_PREFIX)]
    return spans


def device_ops(profile):
    """``[[(name, start_s, end_s)]]``, one list per device plane, in the
    order of the device ids."""
    planes = sorted((p for p in profile.planes if DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(p.name.rsplit(":", 1)[1]))
    out = []
    for plane in planes:
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += _events(line)
        out.append(ops)
    return out


def summarize(profile, top: int = 10) -> Summary:
    spans = host_spans(profile)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    _, lo, hi = windows[0]
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    busy, exposed, gaps, op_self = [], [], [], {}
    for ops in device_ops(profile):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if e > lo and s < hi]
        merged = union([(s, e) for _, s, e in ops])
        busy.append(length(merged))
        for name, t in self_times(ops).items():
            op_self[name] = op_self.get(name, 0.0) + t
        coll = union([(s, e) for n, s, e in ops if COLLECTIVE.search(n)])
        other = union([(s, e) for n, s, e in ops if not COLLECTIVE.search(n)])
        exposed.append(length(subtract(coll, other)))
        for s, e in subtract([(lo, hi)], merged):
            mid = 0.5 * (s + e)
            # innermost: the covering span that started last
            label = max(((a, n) for n, a, b in inner if a <= mid <= b),
                        default=(lo, "host"))[1]
            gaps.append((label, e - s))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=hi - lo, busy_s=busy, op_self_s=op_self,
                   exposed_collective_s=exposed, idle_gaps=gaps[:top])


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def short_name(op: str, limit: int = 160) -> str:
    """An op's HLO text without its layouts, cut to ``limit`` letters:
    ``%fusion.16 = f32[4194304] fusion(s32[67108864] %p.1, ...``."""
    return re.sub(r"\{[^{}]*\}", "", op)[:limit]


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.op_self_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_name(n), t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in summary.idle_gaps[:top]]}
