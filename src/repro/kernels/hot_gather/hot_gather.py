"""Pallas TPU kernel: VMEM-pinned hot-region gather (GRASP, kernel tier).

The High Reuse Region (first ``hot_size`` rows of the DBG-reordered
Property Array) is one whole-array VMEM operand: it is copied from HBM once
per call and stays resident across the whole grid (the TPU-native analogue
of "protected from thrashing"). Each grid step gathers one tile of indices
against the pinned table; indices outside the hot region produce zeros and
are fixed up by the cold path in ops.py.

TPU mapping notes:
  * d (feature width) is padded to a multiple of 128 (lane dim) by callers.
  * each index tile sits in SMEM and every row is one dynamic-offset
    (1, d) VMEM load and store: pure data movement, so hot rows equal
    ``jnp.take`` bit for bit. Mosaic refuses ``jnp.take`` on a VMEM block.
  * XLA lays a 1-D int32 array out in tiles of ``IDX_TILE`` elements on
    TPU, and Mosaic refuses a 1-D block that is not a multiple of it.
  * VMEM: the pinned block plus the double-buffered output tile must fit
    the kernel's scoped VMEM; ``core.plan.kernel_hot_rows`` sizes it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

IDX_TILE = 1024


def _hot_gather_kernel(idx_ref, hot_ref, out_ref):
    hot_size = hot_ref.shape[0]
    out_ref[...] = jnp.zeros_like(out_ref)

    def row(r, carry):
        i = idx_ref[r]

        @pl.when((i >= 0) & (i < hot_size))
        def _():
            out_ref[pl.ds(r, 1), :] = hot_ref[pl.ds(i, 1), :]

        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], row, 0)


@functools.partial(jax.jit, static_argnames=("tile_e", "interpret"))
def hot_gather_hot_part(
    hot_table: jnp.ndarray,   # (H, d) — the pinned High Reuse Region
    idx: jnp.ndarray,         # (E,) int32, full index stream (hot + cold)
    tile_e: int = IDX_TILE,
    interpret: Optional[bool] = None,   # None: decided by the platform
) -> jnp.ndarray:
    h, d = hot_table.shape
    e = idx.shape[0]
    assert e % tile_e == 0, f"E={e} must be divisible by tile_e={tile_e}"
    if interpret is None:
        interpret = kernels.interpret()
    return pl.pallas_call(
        _hot_gather_kernel,
        grid=(e // tile_e,),
        in_specs=[
            pl.BlockSpec((tile_e,), lambda i: (i,),
                         memory_space=pltpu.SMEM),          # index tile
            pl.BlockSpec(memory_space=pltpu.VMEM),          # pinned hot block
        ],
        out_specs=pl.BlockSpec((tile_e, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e, d), hot_table.dtype),
        interpret=interpret,
    )(idx, hot_table)


def _gather_seg_kernel(idx_ref, seg_ref, hot_ref, out_ref, *, hot_size: int,
                       seg_per_tile: int):
    """Fused gather + local segment-sum: edges are CSR-ordered, so each edge
    tile touches a bounded contiguous destination range handled as a local
    one-hot matmul (MXU-friendly) accumulated into the output tile."""
    i = pl.program_id(0)
    idx = idx_ref[...]
    seg = seg_ref[...]
    safe = jnp.clip(idx, 0, hot_size - 1)
    rows = jnp.take(hot_ref[...], safe, axis=0)
    hit = (idx >= 0) & (idx < hot_size)
    rows = jnp.where(hit[:, None], rows, 0.0)
    local_seg = seg - i * seg_per_tile
    onehot = (local_seg[None, :] == jnp.arange(seg_per_tile)[:, None]).astype(
        rows.dtype
    )
    out_ref[...] = jnp.dot(onehot, rows, preferred_element_type=jnp.float32).astype(
        out_ref.dtype
    )


@functools.partial(
    jax.jit, static_argnames=("num_segments", "tile_e", "seg_per_tile")
)
def hot_gather_segment_sum(
    hot_table: jnp.ndarray,
    idx: jnp.ndarray,
    seg: jnp.ndarray,          # (E,) destination of each edge, sorted asc.
    num_segments: int,
    tile_e: int = 2048,
    seg_per_tile: int = 256,
) -> jnp.ndarray:
    """Fused hot gather + segment-sum. Requires an aligned edge layout where
    tile i only holds edges with seg in [i*seg_per_tile, (i+1)*seg_per_tile)
    (built by ops.build_aligned_edges — padding with idx=-1)."""
    h, d = hot_table.shape
    e = idx.shape[0]
    grid = (e // tile_e,)
    assert grid[0] * seg_per_tile == num_segments
    return pl.pallas_call(
        functools.partial(
            _gather_seg_kernel, hot_size=h, seg_per_tile=seg_per_tile
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_e,), lambda i: (i,)),
            pl.BlockSpec((tile_e,), lambda i: (i,)),
            pl.BlockSpec((h, d), lambda i: (0, 0)),       # pinned hot block
        ],
        out_specs=pl.BlockSpec((seg_per_tile, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), jnp.float32),
        interpret=kernels.interpret(),
    )(idx, seg, hot_table)
