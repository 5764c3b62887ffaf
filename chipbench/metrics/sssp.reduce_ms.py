"""Device time of SSSP's segment-min into the vertices (the
``edge_map.reduce`` scope) per round (ms), over the rounds the program
counted in the traced window."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "sssp", "sssp", "edge_map.reduce",
                           per="rounds")
