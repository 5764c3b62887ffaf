"""Useful over attempted relaxations of the traced SSSP window (%): the
out-arcs of each round's frontier over the arc slots the round's edge map
processed, summed over the window's queries, from the program's own
counters (``frontier_arcs``, ``arcs_relaxed``). It bounds what an edge map
that visits only the frontier's arcs could save."""
from chipbench import scopes


def read(ctx):
    calls = scopes.window_calls(ctx, "sssp", "sssp")
    if calls is None:
        return None
    counts = scopes.counts(calls)
    attempted = sum(int(c["arcs_relaxed"].sum()) for c in counts)
    if attempted <= 0:
        return None
    return 100.0 * sum(int(c["frontier_arcs"].sum()) for c in counts) \
        / attempted
