"""Device time of PageRank's property gather (the ``edge_map.gather``
scope) per iteration (ms), over the iterations the program counted in the
traced window."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "pagerank", "pagerank", "edge_map.gather",
                           per="iterations")
