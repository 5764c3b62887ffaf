"""NN substrate: transformer decode==forward consistency, GNN equivariance,
MoE routing semantics, MIND shapes/gradients."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cfgs
from repro.nn import gnn as gnn_mod
from repro.nn import layers as L
from repro.nn import recsys as recsys_mod
from repro.nn import transformer as tfm


@pytest.fixture(scope="module")
def tiny_cfg():
    return cfgs.LMConfig(
        name="tiny", n_layers=2, d_model=32, n_heads=4, n_kv=2, d_ff=64,
        vocab=97, act="silu", gated=True, remat=False, microbatches=1,
    )


@pytest.mark.parametrize("compute_dtype, precision", [
    (jnp.float32, (jax.lax.Precision.HIGHEST,) * 2),
    (jnp.bfloat16, None),
])
def test_dense_float32_asks_for_float32_products(compute_dtype, precision):
    """A float32 ``compute_dtype`` must not run as one bfloat16 pass on a
    TPU; bfloat16 keeps the default."""
    p = L.dense_init(jax.random.PRNGKey(0), 8, 4)
    jaxpr = jax.make_jaxpr(lambda x: L.dense(p, x, compute_dtype))(
        jnp.ones((2, 8)))
    (dot,) = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dot.params["precision"] == precision


def test_decode_matches_forward(tiny_cfg):
    """Teacher-forcing equivalence: full forward logits at position t ==
    decode-with-cache logits after consuming t tokens. This pins down RoPE
    offsets, causal masking and the cache update in one test."""
    cfg = tiny_cfg
    key = jax.random.PRNGKey(0)
    params = tfm.init(key, cfg)
    tokens = jax.random.randint(key, (2, 12), 0, cfg.vocab, dtype=jnp.int32)

    full_logits, _ = tfm.forward(params, cfg, tokens)
    # prefill on the first 8, decode the next 4
    logits_p, cache = tfm.prefill(params, cfg, tokens[:, :8], max_len=16)
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(full_logits[:, 7]), rtol=0.06, atol=5e-2
    )
    for t in range(8, 12):
        logits_d, cache = tfm.decode_step(params, cfg, cache, tokens[:, t])
        np.testing.assert_allclose(
            np.asarray(logits_d), np.asarray(full_logits[:, t]),
            rtol=0.06, atol=5e-2,
        )


def test_chunked_loss_matches_full(tiny_cfg):
    cfg = tiny_cfg
    key = jax.random.PRNGKey(1)
    params = tfm.init(key, cfg)
    b = {
        "tokens": jax.random.randint(key, (2, 1024), 0, cfg.vocab, jnp.int32),
        "labels": jax.random.randint(key, (2, 1024), 0, cfg.vocab, jnp.int32),
    }
    loss = tfm.loss_fn(params, cfg, b)  # 1024 -> 2 chunks
    logits, aux = tfm.forward(params, cfg, b["tokens"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, b["labels"][..., None], axis=-1)[..., 0]
    ref = -ll.mean() + 0.01 * aux
    assert float(jnp.abs(loss - ref)) < 1e-3


def test_train_step_reduces_loss(tiny_cfg):
    from repro.train import optimizer as opt_mod

    cfg = tiny_cfg
    key = jax.random.PRNGKey(2)
    params = tfm.init(key, cfg)
    opt_init, opt_update = opt_mod.make(opt_mod.OptConfig(name="adamw", lr=3e-3))
    opt_state = opt_init(params)
    b = {
        "tokens": jax.random.randint(key, (4, 64), 0, cfg.vocab, jnp.int32),
        "labels": jax.random.randint(key, (4, 64), 0, cfg.vocab, jnp.int32),
    }

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(tfm.loss_fn)(p, cfg, b)
        p, o = opt_update(g, o, p)
        return p, o, loss

    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_moe_routing_topk_mass():
    key = jax.random.PRNGKey(0)
    p = L.moe_init(key, 16, 32, n_experts=4, gated=True)
    x = jax.random.normal(key, (64, 16))
    out, aux = L.moe(p, x, top_k=2, capacity_factor=2.0)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0.0  # load-balance loss is positive


def test_moe_capacity_drop_is_bounded():
    """With capacity_factor=2 and uniform tokens, dropped mass ~ 0: MoE out
    should differ from zero for nearly all tokens."""
    key = jax.random.PRNGKey(1)
    p = L.moe_init(key, 8, 16, n_experts=4, gated=False)
    x = jax.random.normal(key, (256, 8))
    out, _ = L.moe(p, x, top_k=1, capacity_factor=2.0)
    nonzero = np.asarray(jnp.abs(out).sum(axis=-1) > 0)
    assert nonzero.mean() > 0.95


def _rot():
    # a fixed 3D rotation matrix
    a, b, c = 0.3, 1.1, -0.7
    rx = np.array([[1, 0, 0], [0, np.cos(a), -np.sin(a)], [0, np.sin(a), np.cos(a)]])
    ry = np.array([[np.cos(b), 0, np.sin(b)], [0, 1, 0], [-np.sin(b), 0, np.cos(b)]])
    rz = np.array([[np.cos(c), -np.sin(c), 0], [np.sin(c), np.cos(c), 0], [0, 0, 1]])
    return (rx @ ry @ rz).astype(np.float32)


def _mol_batch(rng, n=20, e=60, d=8):
    return {
        "x": rng.standard_normal((n, d)).astype(np.float32),
        "src": rng.integers(0, n, e).astype(np.int32),
        "dst": rng.integers(0, n, e).astype(np.int32),
        "emask": np.ones(e, bool),
        "coords": rng.standard_normal((n, 3)).astype(np.float32),
        "species": rng.integers(0, 8, n).astype(np.int32),
    }


def test_egnn_equivariance():
    cfg = cfgs.GNNConfig(name="t", kind="egnn", n_layers=2, d_hidden=16)
    rng = np.random.default_rng(0)
    batch = _mol_batch(rng)
    params = gnn_mod.init(jax.random.PRNGKey(0), cfg, d_feat=8)
    h1, c1 = gnn_mod.apply(params, cfg, batch)
    R = _rot()
    b2 = dict(batch, coords=batch["coords"] @ R.T)
    h2, c2 = gnn_mod.apply(params, cfg, b2)
    # invariant features, equivariant coordinates
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(c1) @ R.T, np.asarray(c2), atol=2e-4)


def test_egnn_translation_equivariance():
    cfg = cfgs.GNNConfig(name="t", kind="egnn", n_layers=2, d_hidden=16)
    rng = np.random.default_rng(1)
    batch = _mol_batch(rng)
    params = gnn_mod.init(jax.random.PRNGKey(0), cfg, d_feat=8)
    h1, c1 = gnn_mod.apply(params, cfg, batch)
    shift = np.array([5.0, -3.0, 2.0], np.float32)
    b2 = dict(batch, coords=batch["coords"] + shift)
    h2, c2 = gnn_mod.apply(params, cfg, b2)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(c1) + shift, np.asarray(c2), atol=2e-4)


def test_nequip_rotation_invariance():
    cfg = cfgs.GNNConfig(name="t", kind="nequip", n_layers=2, d_hidden=8,
                         l_max=2, n_rbf=4, cutoff=5.0)
    rng = np.random.default_rng(2)
    batch = _mol_batch(rng)
    params = gnn_mod.init(jax.random.PRNGKey(0), cfg, d_feat=8)
    e1 = gnn_mod.apply(params, cfg, batch)
    R = _rot()
    b2 = dict(batch, coords=batch["coords"] @ R.T)
    e2 = gnn_mod.apply(params, cfg, b2)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=1e-4)


def test_gin_isomorphism_sum_agg():
    """GIN with sum aggregation distinguishes multisets: doubling an edge
    changes the target's embedding (mean-agg would not for equal msgs)."""
    cfg = cfgs.GNNConfig(name="t", kind="gin", n_layers=1, d_hidden=8)
    params = gnn_mod.init(jax.random.PRNGKey(0), cfg, d_feat=4)
    x = np.ones((3, 4), np.float32)
    b1 = {"x": x, "src": np.array([1], np.int32), "dst": np.array([0], np.int32),
          "emask": np.ones(1, bool)}
    b2 = {"x": x, "src": np.array([1, 2], np.int32),
          "dst": np.array([0, 0], np.int32), "emask": np.ones(2, bool)}
    o1 = np.asarray(gnn_mod.apply(params, cfg, b1))
    o2 = np.asarray(gnn_mod.apply(params, cfg, b2))
    assert np.abs(o1[0] - o2[0]).max() > 1e-5


def test_pna_aggregators_shapes():
    cfg = cfgs.GNNConfig(name="t", kind="pna", n_layers=2, d_hidden=16)
    rng = np.random.default_rng(3)
    batch = _mol_batch(rng, n=30, e=100, d=8)
    params = gnn_mod.init(jax.random.PRNGKey(0), cfg, d_feat=8)
    out = gnn_mod.apply(params, cfg, batch)
    assert out.shape == (30, cfg.d_out)
    assert np.isfinite(np.asarray(out)).all()


def test_mind_interests_and_loss():
    cfg = cfgs.reduced(cfgs.RecsysConfig(name="mind"))
    params = recsys_mod.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    hist = rng.integers(0, cfg.n_items, (16, cfg.hist_len)).astype(np.int32)
    mask = np.ones_like(hist, bool)
    interests = recsys_mod.user_interests(params, cfg, jnp.asarray(hist),
                                          jnp.asarray(mask))
    assert interests.shape == (16, cfg.n_interests, cfg.embed_dim)
    batch = {
        "hist": jnp.asarray(hist), "hist_mask": jnp.asarray(mask),
        "target": jnp.asarray(rng.integers(0, cfg.n_items, 16, ).astype(np.int32)),
        "negatives": jnp.asarray(rng.integers(0, cfg.n_items, 32).astype(np.int32)),
    }
    loss, grads = jax.value_and_grad(recsys_mod.loss_fn)(params, cfg, batch)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.abs(g).sum()) for g in jax.tree_util.tree_leaves(grads))
    assert gn > 0.0


def test_mind_serve_and_retrieval_consistency():
    cfg = cfgs.reduced(cfgs.RecsysConfig(name="mind"))
    params = recsys_mod.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    hist = jnp.asarray(rng.integers(0, cfg.n_items, (1, cfg.hist_len)).astype(np.int32))
    mask = jnp.ones_like(hist, dtype=bool)
    cands = jnp.asarray(rng.integers(0, cfg.n_items, 128).astype(np.int32))
    serve = recsys_mod.serve_scores(
        params, cfg, {"hist": hist, "hist_mask": mask,
                      "candidates": cands[None, :]})
    retr = recsys_mod.retrieval_scores(
        params, cfg, {"hist": hist, "hist_mask": mask, "candidates": cands})
    np.testing.assert_allclose(np.asarray(serve), np.asarray(retr), rtol=1e-5)
