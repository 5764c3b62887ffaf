"""Share (%) of the traced PageRank window in which no operation ran on
the device: 1 minus the union of device op intervals over the window."""


def read(ctx):
    if ctx["job"] != "pagerank" or ctx["trace"] is None:
        return None
    return ctx["trace"].idle_pct
