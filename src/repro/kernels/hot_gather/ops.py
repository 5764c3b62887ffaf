"""Jitted wrappers composing the hot-region Pallas kernel with the bounded
cold-path fixup (the full GRASP two-tier gather).

Cold fixup: indices >= hot_size are compacted into a capacity-bounded
buffer (skew guarantees the cold fraction is small — paper Table I: hot
vertices cover 81-93% of edges), gathered from HBM once, and scattered
back. ``cold_capacity`` bounds the HBM traffic; on no-skew inputs callers
size it at E (graceful degradation, paper Fig. 9).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_mod
from repro.kernels.hot_gather.hot_gather import (
    IDX_TILE,
    hot_gather_hot_part,
    hot_gather_segment_sum,
)

LANE = 128


def _pad_rows(e: int, tile: int) -> int:
    return (e + tile - 1) // tile * tile


@functools.partial(jax.jit, static_argnames=("hot_size", "cold_capacity",
                                             "tile_e"))
def hot_gather(
    prop: jnp.ndarray,         # (N, d)
    idx: jnp.ndarray,          # (E,) int32
    hot_size: Optional[int] = None,
    cold_capacity: Optional[int] = None,
    tile_e: int = 2 * IDX_TILE,
) -> jnp.ndarray:
    """Drop-in replacement for ``jnp.take(prop, idx, axis=0)``.

    ``hot_size=None`` pins the largest block the kernel's VMEM allows."""
    n, d = prop.shape
    e = idx.shape[0]
    d_pad = (d + LANE - 1) // LANE * LANE
    if hot_size is None:
        hot_size = plan_mod.kernel_hot_rows(d_pad * prop.dtype.itemsize,
                                            tile_e)
    hot_size = min(hot_size, n)
    if cold_capacity is None:
        cold_capacity = e  # exact by default; plans shrink it via skew

    e_pad = _pad_rows(e, tile_e)
    hot = jnp.pad(prop[:hot_size], ((0, 0), (0, d_pad - d)))
    idx_p = jnp.pad(idx, (0, e_pad - e), constant_values=-1)

    out = hot_gather_hot_part(hot, idx_p, tile_e=tile_e)
    out = out[:e, :d]

    # --- bounded cold fixup (HBM gather of the compacted cold indices) ---
    cold = idx >= hot_size
    pos = jnp.cumsum(cold.astype(jnp.int32)) - 1          # slot per cold idx
    slot = jnp.where(cold & (pos < cold_capacity), pos, cold_capacity)
    comp = jnp.zeros((cold_capacity + 1,), idx.dtype).at[slot].set(idx)
    cold_rows = jnp.take(prop, comp[:cold_capacity], axis=0)
    cold_rows = jnp.concatenate(
        [cold_rows, jnp.zeros((1, d), prop.dtype)], axis=0
    )
    fix = jnp.take(cold_rows, jnp.minimum(slot, cold_capacity), axis=0)
    return jnp.where(cold[:, None], fix, out)


def build_aligned_edges(indptr: np.ndarray, indices: np.ndarray,
                        seg_per_tile: int, tile_e: int):
    """Host-side layout pass: pack CSR edges into tiles such that tile i only
    contains destinations [i*seg_per_tile, (i+1)*seg_per_tile), padding with
    idx=-1. Returns (idx_tiles, seg_tiles, num_segments_padded)."""
    n = indptr.shape[0] - 1
    n_pad = (n + seg_per_tile - 1) // seg_per_tile * seg_per_tile
    n_tiles = n_pad // seg_per_tile
    out_idx, out_seg = [], []
    for t in range(n_tiles):
        lo_v, hi_v = t * seg_per_tile, min((t + 1) * seg_per_tile, n)
        sl = slice(indptr[lo_v], indptr[hi_v])
        e_idx = indices[sl]
        e_seg = np.repeat(
            np.arange(lo_v, hi_v), np.diff(indptr[lo_v : hi_v + 1])
        )
        # split oversized tiles into multiple chunks of tile_e
        for off in range(0, max(len(e_idx), 1), tile_e):
            chunk_i = e_idx[off : off + tile_e]
            chunk_s = e_seg[off : off + tile_e]
            pad = tile_e - len(chunk_i)
            out_idx.append(np.pad(chunk_i, (0, pad), constant_values=-1))
            out_seg.append(np.pad(chunk_s, (0, pad), constant_values=lo_v))
    return (
        np.concatenate(out_idx).astype(np.int32),
        np.concatenate(out_seg).astype(np.int32),
        n_pad,
    )


def hot_gather_segsum_aligned(
    hot_table: jnp.ndarray,
    idx_tiles: jnp.ndarray,
    seg_tiles: jnp.ndarray,
    num_segments: int,
    seg_per_tile: int,
    tile_e: int = 2048,
) -> jnp.ndarray:
    """Fused hot gather + segment-sum over a pre-aligned edge layout.

    Multiple tiles may map to the same output block (oversized vertex
    ranges); pallas accumulates via the revisiting-output pattern only when
    the grid is ordered, so we instead sum duplicate tiles outside: callers
    with heavy-hub tiles use ops.hot_gather + segment_sum. This fused path
    asserts one tile per segment block.
    """
    d_pad = (hot_table.shape[1] + LANE - 1) // LANE * LANE
    hot = jnp.pad(hot_table, ((0, 0), (0, d_pad - hot_table.shape[1])))
    out = hot_gather_segment_sum(
        hot, idx_tiles, seg_tiles, num_segments,
        tile_e=tile_e, seg_per_tile=seg_per_tile,
    )
    return out[:, : hot_table.shape[1]]
