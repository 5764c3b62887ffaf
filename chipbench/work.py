"""The least work a job must do, counted from the reference's answer and
the graph's shape, whatever implements it. The rooflines divide these
counts by the device time the trace measured."""
from __future__ import annotations

import numpy as np


def pagerank_least_bytes(num_nodes: int, num_edges: int,
                         iterations: int) -> int:
    """HBM bytes of ``iterations`` pull PageRank iterations: every edge's
    source id read once (4 B), and per vertex its CSR offset, out-degree
    and rank read once and its new rank written once (16 B)."""
    return iterations * (4 * num_edges + 16 * num_nodes)


def sssp_least_bytes(out_degree: np.ndarray, reached: np.ndarray) -> int:
    """HBM bytes of one SSSP query: each out-edge of every reached vertex
    read once, target and weight (8 B), and per reached vertex its offset
    read and its distance read and written (12 B)."""
    return int(8 * out_degree[reached].sum() + 12 * reached.sum())


def hbm_roofline(ctx, job: str):
    """Share (%) of the HBM roofline for a traced window of ``job``: the
    least bytes its jobs had to move over the chip's published HBM
    bandwidth times the device busy time. None where there is nothing to
    read: no trace, another job, or no device time."""
    summary = ctx["trace"]
    least = ctx["work"].get("least_bytes")
    if summary is None or ctx["job"] != job or not least:
        return None
    busy = sum(summary.busy_s)
    if busy <= 0:
        return None
    return 100.0 * least / (ctx["peaks"]["hbm_bytes_per_s"] * busy)
