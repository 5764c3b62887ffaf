"""Device time by program scope, and the program's own counts, for the
per-layer readers.

The device ops of a trace carry XLA's instruction names: each key of
``Summary.op_self_s`` is an op's HLO text, ``%fusion.16 = f32[...]
fusion(...)``, whose instruction name is the token before `` = ``. The
program (``repro.obs``) maps the instruction names of the program a call
ran to the scope the program put it under (``edge_map.gather``, ...), and
keeps each call's counters (iterations, rounds, relaxations). This
module puts the two together for the calls of the traced window.

The app runs once in set-up and then once per job in the window, and not
again before the readers run, so the window's calls are the last
``ctx["jobs"]`` the program kept. Every reader returns None where there is
nothing to read: another job, no trace, no device time in it (a CPU run),
a program that keeps no calls (one older than ``repro.obs``) or fewer than
the jobs, a window that ran more than one program, or no time in the scope
(the work is gone, which is not 0 ms of it).
"""
from __future__ import annotations

UNSCOPED = "unscoped"


def instruction(op: str) -> str:
    """``'%fusion.16 = f32[4] fusion(...)'`` -> ``'fusion.16'``."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def self_seconds(op_self_s: dict, scope_of: dict) -> dict:
    """{scope: device self seconds} over the ops of ``op_self_s``, with the
    ops that ``scope_of`` (instruction name -> scope or None) gives no scope,
    or does not know, under :data:`UNSCOPED`."""
    out = {}
    for op, t in op_self_s.items():
        scope = scope_of.get(instruction(op)) or UNSCOPED
        out[scope] = out.get(scope, 0.0) + t
    return out


def window_calls(ctx, job: str, app: str):
    """The calls of ``app`` that the traced window of ``job`` ran, or None."""
    summary = ctx["trace"]
    if ctx["job"] != job or summary is None or sum(summary.busy_s) <= 0:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    calls, jobs = obs.calls(app), ctx["jobs"]
    if jobs < 1 or len(calls) < jobs:
        return None
    return calls[-jobs:]


def counts(calls) -> list:
    from repro import obs

    return [obs.counts(c) for c in calls]


def scope_ms(ctx, job: str, app: str, scope: str, per: str | None = None):
    """Device ms under ``scope`` in the traced window of ``job``, over the
    sum of the counter ``per`` across the window's calls, or over the jobs
    where ``per`` is None."""
    calls = window_calls(ctx, job, app)
    if calls is None or len({c.key for c in calls}) != 1:
        return None
    from repro import obs

    seconds = self_seconds(ctx["trace"].op_self_s,
                           obs.scope_map(calls[0])).get(scope, 0.0)
    units = len(calls) if per is None else sum(
        int(c[per]) for c in counts(calls))
    if seconds <= 0 or units <= 0:
        return None
    return 1000.0 * seconds / units
