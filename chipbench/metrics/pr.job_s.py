"""PageRank time to solution: the whole measured window over the jobs it
completed, on the host clock. The window runs jobs back to back and lets
the last one finish, so this is the mean job time over all the work of
the window."""


def read(ctx):
    if ctx["job"] != "pagerank" or not ctx["jobs"]:
        return None
    return ctx["window_s"] / ctx["jobs"]
