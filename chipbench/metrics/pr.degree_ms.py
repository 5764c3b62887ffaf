"""Device time of PageRank's out-degree count (the ``pagerank.out_degree``
scope) per job (ms): every job counts the degrees again."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "pagerank", "pagerank", "pagerank.out_degree")
