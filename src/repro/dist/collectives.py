"""GRASP-aware graph partitioning and the distributed GIN exchange.

The layout lifts the paper's Table I skew property to the partition tier.
After DBG reordering the hot vertices are a prefix of the id space and
cover the large majority of edge *sources*, so each device keeps a
three-region feature table:

    [0, hot)                        replicated hot prefix (every device)
    [hot, hot + cold_per_dev)       this device's own cold slice
    [hot + cold_per_dev, table_len) halo: published remote-cold rows,
                                    P contiguous per-owner blocks of c_pub

Edges live on the device that owns their destination (pull-based
aggregation), so only cold remote *sources* ever cross the network — the
minority path by construction. Per layer the exchange is two all_gathers:
own-hot slices -> full hot table, and each owner's published cold rows ->
the halo.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PSpec

from repro.core import plan as plan_mod
from repro.nn import gnn as gnn_mod
from repro.nn import layers as L

# Per-device HBM each replica spends on the shared hot prefix. 64MB out of
# a v5e-class 16GB keeps replication cost <0.5% of device memory while
# covering the paper's Table I hot sets at 4B/elem.
HOT_REPLICA_BUDGET_BYTES = 64 << 20


@dataclasses.dataclass(frozen=True)
class GraspPartitionSpec:
    """Static shapes of a GRASP partition over `num_devices` devices.

    `num_nodes` is the padded node count (hot + num_devices*cold_per_dev);
    `n_own` nodes live on each device (its hot slice + its cold slice);
    `c_pub` bounds how many cold rows any owner publishes into the halo;
    `e_loc` bounds the per-device edge table; `table_len` is the local
    gather-table length hot + cold_per_dev + num_devices*c_pub.
    """
    num_devices: int
    num_nodes: int
    hot: int
    hot_per_dev: int
    cold_per_dev: int
    n_own: int
    c_pub: int
    e_loc: int
    table_len: int
    pub_frac: float
    edge_slack: float


def partition_spec_for(num_nodes: int, num_edges: int, num_devices: int,
                       hot: Optional[int] = None, pub_frac: float = 0.25,
                       edge_slack: float = 1.5,
                       hot_budget_bytes: Optional[int] = None,
                       elem_bytes: int = 4) -> GraspPartitionSpec:
    """Size the static buffers for a `num_devices`-way GRASP partition.

    `hot` may be given directly (tests / ablations) or derived from a real
    per-device memory budget: with `hot=None`, the replicated hot prefix is
    sized as `entries_for_budget(hot_budget_bytes, elem_bytes)` — the bytes
    each device can afford to spend on the replica, divided by the feature
    row size (`HOT_REPLICA_BUDGET_BYTES` when unspecified).

    `hot` is rounded down to a multiple of `num_devices`; the cold remainder
    is padded up so every device owns exactly `cold_per_dev` cold nodes.
    `pub_frac` scales the halo capacity (1.0 => any cold row may be
    published); `edge_slack` scales the per-device edge budget relative to
    a perfectly balanced split.
    """
    if num_devices < 1:
        raise ValueError("need at least one device")
    if hot is None:
        budget = (HOT_REPLICA_BUDGET_BYTES if hot_budget_bytes is None
                  else hot_budget_bytes)
        hot = plan_mod.entries_for_budget(budget, elem_bytes,
                                          max_entries=num_nodes)
    hot = int(max(0, min(hot, num_nodes)))
    hot -= hot % num_devices
    hot_per_dev = hot // num_devices
    cold = num_nodes - hot
    cold_per_dev = -(-cold // num_devices)  # ceil; 0 iff everything is hot
    padded = hot + num_devices * cold_per_dev
    if cold_per_dev > 0:
        c_pub = int(min(cold_per_dev, max(1, math.ceil(pub_frac * cold_per_dev))))
    else:
        c_pub = 0
    e_loc = max(1, math.ceil(edge_slack * num_edges / num_devices))
    return GraspPartitionSpec(
        num_devices=num_devices,
        num_nodes=padded,
        hot=hot,
        hot_per_dev=hot_per_dev,
        cold_per_dev=cold_per_dev,
        n_own=hot_per_dev + cold_per_dev,
        c_pub=c_pub,
        e_loc=e_loc,
        table_len=hot + cold_per_dev + num_devices * c_pub,
        pub_frac=float(pub_frac),
        edge_slack=float(edge_slack),
    )


def grasp_partition(g, spec: GraspPartitionSpec) -> Dict[str, np.ndarray]:
    """Build per-device edge tables addressing the three-region layout.

    Returns `esrc`/`edst`/`emask` of shape (P, e_loc) — local table indices
    and a validity mask, edges kept in CSR (dst-sorted) order so the
    distributed segment_sum reduces in the same order as the reference —
    plus `pub` (P, c_pub) of published *global* cold ids (0 = empty slot;
    id 0 is always hot or owned, never published), `dropped` (edges lost to
    halo/edge-budget overflow) and `total_edges`.
    """
    P = spec.num_devices
    hot, hpd, cpd = spec.hot, spec.hot_per_dev, spec.cold_per_dev
    src = np.asarray(g.indices, dtype=np.int64)
    dst = np.asarray(g.dst_ids(), dtype=np.int64)
    if g.num_nodes > spec.num_nodes:
        raise ValueError("spec was sized for a smaller graph")

    hpd_ = max(hpd, 1)  # avoid 0-division in unselected np.where branches
    cpd_ = max(cpd, 1)
    owner = np.where(dst < hot, dst // hpd_, (dst - hot) // cpd_)
    dst_local = np.where(dst < hot, dst - owner * hpd,
                         hpd + (dst - hot) - owner * cpd)
    src_owner = np.where(src < hot, -1, (src - hot) // cpd_)  # -1: hot (free)
    remote = src_owner != np.where(src < hot, -1, owner)
    remote &= src_owner >= 0

    # publish lists: per owner, the unique cold ids some other device needs
    pub = np.zeros((P, spec.c_pub), np.int32)
    halo_slot = np.full(spec.num_nodes, -1, np.int64)
    for q in range(P):
        ids = np.unique(src[remote & (src_owner == q)])
        n_q = min(ids.size, spec.c_pub)
        pub[q, :n_q] = ids[:n_q]
        halo_slot[ids[:n_q]] = hot + cpd + q * spec.c_pub + np.arange(n_q)

    own_local = hot + (src - hot) - src_owner * cpd  # valid when src is cold
    esrc_val = np.where(src < hot, src,
                        np.where(src_owner == owner, own_local,
                                 halo_slot[src]))
    addressable = esrc_val >= 0  # -1: remote-cold src beyond halo capacity

    esrc = np.zeros((P, spec.e_loc), np.int32)
    edst = np.zeros((P, spec.e_loc), np.int32)
    emask = np.zeros((P, spec.e_loc), bool)
    for p in range(P):
        sel = np.nonzero(addressable & (owner == p))[0]  # keeps CSR order
        k = min(sel.size, spec.e_loc)
        esrc[p, :k] = esrc_val[sel[:k]]
        edst[p, :k] = dst_local[sel[:k]]
        emask[p, :k] = True
    return {
        "esrc": esrc,
        "edst": edst,
        "emask": emask,
        "pub": pub,
        "dropped": int(g.num_edges - int(emask.sum())),
        "total_edges": int(g.num_edges),
    }


def make_grasp_gin_step(spec: GraspPartitionSpec, cfg, d_feat: int,
                        n_classes: int, mesh, opt_update,
                        overlap: bool = True) -> Tuple:
    """A shard_map GIN train step over a GRASP-partitioned graph.

    Batch dict (leading dim of sharded entries = device blocks):
      x_hot  (hot, d)           replicated hot features
      x_cold (P, cold_per_dev, d) own cold features
      esrc/edst/emask (P, e_loc)  local edge tables from `grasp_partition`
      pub    (P, c_pub)          published global cold ids
      labels (P, n_own)          labels in own-table order [hot | cold]

    Returns `(step, batch_specs)`; `step(params, opt_state, batch)` yields
    `(new_params, new_opt_state, {"loss": global_mean_nll})`, numerically
    matching the unpartitioned `gin_apply` loss (same per-destination edge
    order, f32 compute). `batch_specs` maps batch keys to spec-entry tuples
    for `sharding.ns`.

    `overlap=True` (the default) runs the software-pipelined exchange:
    gather tables are double-buffered across layers, layer l+1's hot and
    halo rows travel in ONE fused all_gather issued the moment h_{l+1}
    exists (a full layer of aggregation/MLP compute before the first
    consumer), and layer 0's hot table is `x_hot` itself — it is already
    replicated, so gathering own slices would only reassemble it. The
    forward is pure data movement, but differentiating the fused gather
    sums some gradient contributions in a different order, so loss and
    params match the `overlap=False` sequential step to float32 rounding,
    not bit for bit (collective count per step drops from 2L to L).
    `overlap=False` keeps the original gather-per-region schedule.
    """
    if cfg.kind != "gin":
        raise ValueError(f"grasp exchange step only supports gin, got {cfg.kind!r}")
    if int(mesh.size) != spec.num_devices:
        raise ValueError(f"mesh has {mesh.size} devices, spec wants "
                         f"{spec.num_devices}")
    axes = tuple(mesh.axis_names)
    hot, hpd, cpd = spec.hot, spec.hot_per_dev, spec.cold_per_dev
    P = spec.num_devices

    def fused_exchange(h, pub_local):
        """Double-buffer swap: one all_gather of [own hot slice | published
        cold rows] refreshes both the hot table and the halo for the NEXT
        layer. Issued right after h is produced and consumed a whole layer
        of compute later — the window XLA's latency-hiding scheduler can
        fill on real hardware."""
        d = h.shape[1]
        if spec.c_pub == 0:
            return jax.lax.all_gather(h[:hpd], axes, axis=0, tiled=True), None
        buf = jnp.concatenate([h[:hpd], jnp.take(h[hpd:], pub_local, axis=0)],
                              axis=0)
        g = jax.lax.all_gather(buf, axes, axis=0, tiled=True)
        g = g.reshape(P, hpd + spec.c_pub, d)
        return (g[:, :hpd].reshape(P * hpd, d),
                g[:, hpd:].reshape(P * spec.c_pub, d))

    def local_loss(params, x_hot, x_cold, esrc, edst, emask, pub, labels,
                   p_idx):
        # own table order is [own hot slice | own cold slice]
        h_hot_own = jax.lax.dynamic_slice_in_dim(x_hot, p_idx * hpd, hpd, 0)
        h = jnp.concatenate([h_hot_own, x_cold], axis=0)
        # this device's publish list: global ids -> positions in its own
        # cold slice (empty slots clip to row 0, which no edge addresses
        # through the halo)
        pub_local = jnp.clip(pub - (hot + p_idx * cpd), 0, max(cpd - 1, 0))
        layers = params["layers"]
        if overlap:
            # prologue: only the halo needs a collective before layer 0
            hot_full = x_hot
            halo = None
            if spec.c_pub > 0:
                halo = jax.lax.all_gather(
                    jnp.take(x_cold, pub_local, axis=0), axes, axis=0,
                    tiled=True)
        for li, lp in enumerate(layers):
            own_cold = h[hpd:]
            if overlap:
                parts = [hot_full, own_cold]
                if spec.c_pub > 0:
                    parts.append(halo)
            else:
                parts = [jax.lax.all_gather(h[:hpd], axes, axis=0, tiled=True),
                         own_cold]
                if spec.c_pub > 0:
                    published = jnp.take(own_cold, pub_local, axis=0)
                    parts.append(jax.lax.all_gather(published, axes, axis=0,
                                                    tiled=True))
            table = jnp.concatenate(parts, axis=0)
            msg = jnp.take(table, esrc, axis=0)
            msg = jnp.where(emask[:, None], msg, 0.0)
            agg = jax.ops.segment_sum(msg, edst, num_segments=spec.n_own)
            eps = lp["eps"] if lp["eps"] is not None else 0.0
            h = gnn_mod._mlp(lp["mlp"], (1.0 + eps) * h + agg)
            h = jax.nn.relu(L.layernorm(lp["ln"], h))
            if overlap and li + 1 < len(layers):
                hot_full, halo = fused_exchange(h, pub_local)
        logits = L.dense(params["out"], h, jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return -ll.sum() / spec.num_nodes  # global mean after psum

    def sharded_step(params, opt_state, x_hot, x_cold, esrc, edst, emask,
                     pub, labels):
        # strip the leading device-block dim shard_map leaves on sharded args
        x_cold, esrc, edst, emask, pub, labels = (
            a[0] for a in (x_cold, esrc, edst, emask, pub, labels))
        p_idx = jax.lax.axis_index(axes)  # row-major linear device index
        lval, grads = jax.value_and_grad(local_loss)(
            params, x_hot, x_cold, esrc, edst, emask, pub, labels, p_idx)
        grads = jax.lax.psum(grads, axes)
        lval = jax.lax.psum(lval, axes)
        new_params, new_opt = opt_update(grads, opt_state, params)
        return new_params, new_opt, {"loss": lval}

    edge = PSpec(axes)
    sharded = jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(PSpec(), PSpec(), PSpec(), edge, edge, edge, edge, edge,
                  edge),
        out_specs=(PSpec(), PSpec(), PSpec()),
        check_vma=False,
    )

    def step(params, opt_state, batch):
        return sharded(params, opt_state, batch["x_hot"], batch["x_cold"],
                       batch["esrc"], batch["edst"], batch["emask"],
                       batch["pub"], batch["labels"])

    batch_specs = {
        "x_hot": (), "x_cold": (axes,), "esrc": (axes,), "edst": (axes,),
        "emask": (axes,), "pub": (axes,), "labels": (axes,),
    }
    return step, batch_specs
