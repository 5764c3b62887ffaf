"""Device time of PageRank's segment-sum into the vertices (the
``edge_map.reduce`` scope) per iteration (ms), over the iterations the
program counted in the traced window."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "pagerank", "pagerank", "edge_map.reduce",
                           per="iterations")
