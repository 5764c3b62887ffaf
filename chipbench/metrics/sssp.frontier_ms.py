"""Device time of SSSP's active-flag gather and mask (the
``edge_map.frontier`` scope) per round (ms), over the rounds the program
counted in the traced window."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "sssp", "sssp", "edge_map.frontier",
                           per="rounds")
