"""NN building blocks: norms, RoPE, GQA attention (chunked/online-softmax),
dense FFN variants and the sort-based MoE layer.

Pure-functional: ``*_init(key, ...) -> params`` and ``*_apply(params, ...)``.
Parameters are plain dicts of jnp arrays so they stack cleanly along a
leading layer axis for ``lax.scan`` (small HLO => fast 512-device compiles).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(key, d_in: int, d_out: int, scale: Optional[float] = None):
    if scale is None:
        scale = 1.0 / np.sqrt(d_in)
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}


def dense(params, x, compute_dtype=jnp.bfloat16):
    """``x @ w`` in ``compute_dtype``. A float32 ``compute_dtype`` gets
    float32 products: the TPU's default precision would round float32
    operands to one bfloat16 pass."""
    precision = (jax.lax.Precision.HIGHEST
                 if jnp.dtype(compute_dtype) == jnp.float32 else None)
    return jnp.einsum(
        "...i,io->...o", x.astype(compute_dtype), params["w"].astype(compute_dtype),
        precision=precision,
    )


def rmsnorm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * params["g"]).astype(x.dtype)


def layernorm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * params["g"] + params["b"]).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., :, None, :]  # broadcast over heads
    sin = sin[..., :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA), memory-efficient online-softmax over KV chunks
# ---------------------------------------------------------------------------
def _repeat_kv(k: jnp.ndarray, groups: int):
    # (B, S, KV, hd) -> (B, S, KV*groups, hd)
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, hd)).reshape(
        b, s, kv * groups, hd
    )


def attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Sk, KV, hd)
    v: jnp.ndarray,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    kv_chunk: int = 1024,
    q_chunk: int = 512,
    kv_len: Optional[jnp.ndarray] = None,
):
    """Online-softmax attention; O(chunk) memory, HLO-size O(1) via scan.

    GQA is computed with grouped einsums — KV is NEVER materialized at H
    heads (perf iteration: a broadcast repeat of a seq-sharded KV cache
    forces GSPMD to re-gather the whole cache every layer; the grouped
    form keeps the cache sharded and reduces only the (small) outputs).

    ``q_offset`` is the absolute position of q[0] (decode: cache length).
    ``kv_len`` masks the valid cache prefix during decode.
    """
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    groups = h // kvh
    scale = 1.0 / np.sqrt(hd)
    qg_all = q.reshape(b, sq, kvh, groups, hd)

    if sq == 1:
        # decode: single query against the whole cache in one pass (no scan
        # — keeps softmax psum at layer-scan depth for sharded-KV serving)
        qpos = q_offset + jnp.zeros((1,), jnp.int32)
        s = jnp.einsum("bqngd,bknd->bngqk", qg_all, k)
        s = s.astype(jnp.float32) * scale        # (b, kv, g, 1, Sk)
        kpos = jnp.arange(sk)
        if kv_len is not None:
            s = jnp.where((kpos < kv_len)[None, None, None, None], s, -jnp.inf)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bngqk,bknd->bqngd", p.astype(q.dtype), v)
        return out.reshape(b, sq, h, hd).astype(q.dtype)

    # prefill/train path: repeated-KV head layout (measured better under
    # head-TP than the grouped form, which re-shards on the small KV dim)
    kr = _repeat_kv(k, groups)
    vr = _repeat_kv(v, groups)
    n_kv = max(sk // kv_chunk, 1)
    kv_chunk = sk // n_kv
    kr = kr.reshape(b, n_kv, kv_chunk, h, hd)
    vr = vr.reshape(b, n_kv, kv_chunk, h, hd)

    @jax.checkpoint
    def q_block(qb, qpos):
        # qb: (B, qc, H, hd); qpos: (qc,) absolute positions
        # checkpointed: the backward recomputes this q-chunk's kv scan
        # instead of stashing stacked (q_chunks x kv_chunks) score tensors
        # (perf iteration: cut nemotron train temp memory — EXPERIMENTS §Perf)
        def kv_step(carry, inp):
            m, l, acc = carry
            kc, vc, kidx = inp  # (B, kv_chunk, H, hd), scalar chunk index
            kpos = kidx * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kc).astype(jnp.float32) * scale
            if causal:
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None], s, -jnp.inf)
            if kv_len is not None:
                s = jnp.where((kpos < kv_len)[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qb.dtype), vc
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        qc = qb.shape[1]
        m0 = jnp.full((b, h, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, qc), jnp.float32)
        acc0 = jnp.zeros((b, h, qc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, acc0),
            (
                jnp.moveaxis(kr, 1, 0),
                jnp.moveaxis(vr, 1, 0),
                jnp.arange(n_kv),
            ),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B, qc, H, hd)

    n_q = max(sq // q_chunk, 1)
    q_chunk = sq // n_q
    qs = q.reshape(b, n_q, q_chunk, h, hd)

    def q_step(_, inp):
        qb, qidx = inp
        qpos = q_offset + qidx * q_chunk + jnp.arange(q_chunk)
        return None, q_block(qb, qpos)

    _, outs = jax.lax.scan(q_step, None, (jnp.moveaxis(qs, 1, 0), jnp.arange(n_q)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd)


def gqa_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int):
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model),
    }


# ---------------------------------------------------------------------------
# FFN variants
# ---------------------------------------------------------------------------
def squared_relu(x):
    r = jax.nn.relu(x)
    return r * r


ACTS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu2": squared_relu,
    "relu": jax.nn.relu,
}


def ffn_init(key, d_model: int, d_ff: int, gated: bool):
    ks = jax.random.split(key, 3)
    p = {
        "wi": dense_init(ks[0], d_model, d_ff),
        "wo": dense_init(ks[1], d_ff, d_model),
    }
    if gated:
        p["wg"] = dense_init(ks[2], d_model, d_ff)
    return p


def ffn(params, x, act: str = "gelu", compute_dtype=jnp.bfloat16):
    h = dense(params["wi"], x, compute_dtype)
    h = ACTS[act](h)
    if "wg" in params:
        h = h * dense(params["wg"], x, compute_dtype)
    return dense(params["wo"], h, compute_dtype)


# ---------------------------------------------------------------------------
# Mixture-of-Experts: sort-free capacity dispatch (gather/scatter, no O(T*E*C)
# one-hot matmuls so HLO FLOPs stay honest for the roofline).
# ---------------------------------------------------------------------------
def moe_init(key, d_model: int, d_ff: int, n_experts: int, gated: bool):
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], d_model, n_experts, scale=0.02),
        "wi": jax.random.normal(ks[1], (n_experts, d_model, d_ff)) / np.sqrt(d_model),
        "wo": jax.random.normal(ks[2], (n_experts, d_ff, d_model)) / np.sqrt(d_ff),
    }
    if gated:
        p["wg"] = jax.random.normal(ks[3], (n_experts, d_model, d_ff)) / np.sqrt(
            d_model
        )
    return p


def moe(
    params,
    x: jnp.ndarray,  # (T, d)
    top_k: int,
    act: str = "silu",
    capacity_factor: float = 1.25,
    compute_dtype=jnp.bfloat16,
):
    """Top-k token-choice MoE with capacity-bounded scatter dispatch.

    Returns (out, aux_loss). Tokens beyond an expert's capacity are dropped
    (standard GShard semantics).
    """
    t, d = x.shape
    e = params["router"]["w"].shape[1]
    cap = int(np.ceil(t * top_k / e * capacity_factor))

    logits = dense(params["router"], x, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # slot of each (token, k) within its expert: rank among same-expert
    # picks. Hierarchical cumsum: the big scan runs within token chunks
    # (shard-local under data-parallel sharding) and only the tiny
    # (chunks, E) totals cross shards — a flat global cumsum forced GSPMD
    # into per-layer collective chains (perf log, EXPERIMENTS §Perf).
    flat_e = expert_idx.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (T*k, E)
    n = flat_e.shape[0]
    chunks = 16 if n % 16 == 0 else 1
    oh_c = onehot.reshape(chunks, n // chunks, e)
    local = jnp.cumsum(oh_c, axis=1) - oh_c
    totals = oh_c.sum(axis=1)                         # (chunks, E)
    offs = jnp.cumsum(totals, axis=0) - totals
    rank_mat = (local + offs[:, None, :]).reshape(n, e)
    ranks = rank_mat.max(axis=-1, where=onehot > 0, initial=0)
    # position within expert buffer; overflow -> dropped
    keep = ranks < cap
    slot = jnp.where(keep, flat_e * cap + ranks, e * cap)  # sentinel row

    # scatter tokens into (E*cap + 1, d) buffer
    xk = jnp.repeat(x, top_k, axis=0)  # (T*k, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(xk)
    buf = buf[: e * cap].reshape(e, cap, d)

    h = jnp.einsum(
        "ecd,edf->ecf", buf.astype(compute_dtype), params["wi"].astype(compute_dtype)
    )
    h = ACTS[act](h)
    if "wg" in params:
        g = jnp.einsum(
            "ecd,edf->ecf",
            buf.astype(compute_dtype),
            params["wg"].astype(compute_dtype),
        )
        h = h * g
    y = jnp.einsum(
        "ecf,efd->ecd", h, params["wo"].astype(compute_dtype)
    )  # (E, cap, d)

    y_flat = y.reshape(e * cap, d)
    gathered = jnp.where(
        keep[:, None], jnp.take(y_flat, jnp.minimum(slot, e * cap - 1), axis=0), 0.0
    )
    out = (
        (gathered * gate_vals.reshape(-1)[:, None].astype(gathered.dtype))
        .reshape(t, top_k, d)
        .sum(axis=1)
    )

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (t * top_k)
    aux = e * jnp.sum(me * ce)
    return out.astype(x.dtype), aux
