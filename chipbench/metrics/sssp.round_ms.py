"""Device time per Bellman-Ford round of the traced SSSP window (ms): the
device busy time over the rounds its queries needed, counted from the
float64 distances (``reference.bellman_ford_rounds``). A query's round
count is set by the graph and the source the seed draws; this divides it
out, so a change to the edge map's cost per round shows at the spread of
one round's time."""


def read(ctx):
    summary, rounds = ctx["trace"], ctx["work"].get("rounds")
    if ctx["job"] != "sssp" or summary is None or not rounds:
        return None
    busy = sum(summary.busy_s)
    return 1000.0 * busy / rounds if busy > 0 else None
