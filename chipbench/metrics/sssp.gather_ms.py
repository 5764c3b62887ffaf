"""Device time of SSSP's distance gather (the ``edge_map.gather`` scope)
per round (ms), over the rounds the program counted in the traced
window."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "sssp", "sssp", "edge_map.gather",
                           per="rounds")
