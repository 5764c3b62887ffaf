"""SSSP queries' share of the HBM roofline (%), from the trace and
``work.sssp_least_bytes`` over the vertices Dijkstra reached."""
from chipbench import work


def read(ctx):
    return work.hbm_roofline(ctx, "sssp")
