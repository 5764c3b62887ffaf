"""PageRank jobs' share of the HBM roofline (%), from the trace and
``work.pagerank_least_bytes`` at the float64 reference's iteration count."""
from chipbench import work


def read(ctx):
    return work.hbm_roofline(ctx, "pagerank")
