"""Jitted wrappers: hot-cached embedding lookup / bag with cold fixup."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.plan import GraspPlan
from repro.kernels.embedding_bag.embedding_bag import hot_bag_hot_part
from repro.kernels.hot_gather.ops import hot_gather

LANE = 128


def hot_lookup(table: jnp.ndarray, ids: jnp.ndarray,
               plan: Optional[GraspPlan] = None):
    """(V,d) x (B,) -> (B,d); hot prefix from VMEM, cold fixup bounded.
    Without a plan the hot prefix is the largest block the kernel's VMEM
    allows."""
    return hot_gather(table, ids,
                      hot_size=None if plan is None else plan.hot_size)


@functools.partial(jax.jit, static_argnames=("hot_size", "cold_capacity",
                                             "tile_b"))
def hot_bag(
    table: jnp.ndarray,       # (V, d)
    ids: jnp.ndarray,         # (B, H)
    mask: jnp.ndarray,        # (B, H)
    hot_size: int,
    cold_capacity: Optional[int] = None,
    tile_b: int = 256,
) -> jnp.ndarray:
    """Fused EmbeddingBag(sum): kernel handles hot rows; cold rows are
    compacted, gathered once from HBM and segment-summed into the bags."""
    v, d = table.shape
    b, hlen = ids.shape
    hot_size = min(hot_size, v)
    if cold_capacity is None:
        cold_capacity = b * hlen

    d_pad = (d + LANE - 1) // LANE * LANE
    b_pad = (b + tile_b - 1) // tile_b * tile_b
    hot = jnp.pad(table[:hot_size], ((0, 0), (0, d_pad - d)))
    ids_p = jnp.pad(ids, ((0, b_pad - b), (0, 0)), constant_values=-1)
    mask_p = jnp.pad(mask, ((0, b_pad - b), (0, 0)), constant_values=False)

    out = hot_bag_hot_part(hot, ids_p, mask_p, tile_b=tile_b)[:b, :d]

    # cold fixup: compact cold (id, bag) pairs, gather, segment-sum per bag
    flat_ids = ids.reshape(-1)
    flat_mask = mask.reshape(-1)
    bag_of = jnp.repeat(jnp.arange(b), hlen)
    cold = flat_mask & (flat_ids >= hot_size)
    pos = jnp.cumsum(cold.astype(jnp.int32)) - 1
    slot = jnp.where(cold & (pos < cold_capacity), pos, cold_capacity)
    comp_ids = jnp.zeros((cold_capacity + 1,), flat_ids.dtype).at[slot].set(flat_ids)
    comp_bag = jnp.full((cold_capacity + 1,), b, bag_of.dtype).at[slot].set(bag_of)
    cold_rows = jnp.take(table, comp_ids[:cold_capacity], axis=0)
    fix = jax.ops.segment_sum(
        cold_rows, comp_bag[:cold_capacity], num_segments=b + 1
    )[:b]
    return out + fix
