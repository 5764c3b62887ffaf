"""`make perf-smoke` — the tracked perf baseline for the two hottest paths.

Three sections, every speed number guarded by an equality invariant so a
faster wrong answer can never pass:

  lookup      vectorized ``EmbeddingCache.lookup`` vs the retained
              pre-vectorization loop (``serve.refcache``) over identical
              id streams at several batch sizes and skews. Asserts
              bit-identical outputs (== ``table[ids]``), identical
              hit/miss/bypass counters and cold-region metadata, and the
              acceptance floor: >= 3x rows/s at batch 256 on the
              zipf a=1.1 stream.
  dist        ``make_grasp_gin_step`` pipelined (overlap=True, the
              default) vs sequential (overlap=False) on the simulated
              8-device mesh: asserts loss and params agree to float32
              rounding (1e-6) over multiple steps, reports per-step wall
              time and collective counts (the pipelined exchange issues L
              fused all_gathers per step instead of 2L).
  hot_gather  the Pallas hot-region gather kernel microbench
              (interpret mode on CPU), checked against the dense
              reference gather.

Emits everything to ``BENCH_perf.json`` — the file README perf figures
are refreshed from, and the trajectory regressions are caught against.

    PYTHONPATH=src python -m benchmarks.perf_smoke [--out BENCH_perf.json]

Non-tier-1: wired into scripts/verify.sh after the tier-1 steps.

A CPU-only tool: it forces 8 host CPU devices for the dist section, so it
never runs on a TPU, and every time it reports is a host-CPU time, not a
device measurement. ``chip_smoke.py`` at the repository root is what runs
on the chip.
"""
from __future__ import annotations

import os

# must precede the first jax import: the dist section needs 8 host devices
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time

import numpy as np

LOOKUP_BATCHES = (64, 256, 1024)
LOOKUP_SKEWS = ("uniform", "zipf_1.1", "zipf_1.4")
LOOKUP_ROUNDS = 50
ACCEPT_BATCH, ACCEPT_SKEW, ACCEPT_SPEEDUP = 256, "zipf_1.1", 3.0


def _stream(skew: str, batch: int, n_rows: int, rounds: int, seed: int):
    from repro.data.pipeline import zipf_ids

    rng = np.random.default_rng(seed)
    if skew == "uniform":
        return [rng.integers(0, n_rows, batch) for _ in range(rounds)]
    a = float(skew.split("_")[1])
    return [zipf_ids(rng, (batch,), n_rows, a=a) for _ in range(rounds)]


def bench_lookup():
    """Vectorized vs reference lookup: equivalence pass, then timed pass."""
    from repro.serve.cache import CacheConfig, EmbeddingCache
    from repro.serve.refcache import ReferenceEmbeddingCache

    n_rows, dim = 1000, 16
    cc = CacheConfig(budget_bytes=128 * dim * 4, hot_fraction=0.5,
                     policy="rrpv", use_kernel=False)
    rng = np.random.default_rng(0)
    table = rng.standard_normal((n_rows, dim)).astype(np.float32)

    results = {}
    for skew in LOOKUP_SKEWS:
        for batch in LOOKUP_BATCHES:
            stream = _stream(skew, batch, n_rows, LOOKUP_ROUNDS, seed=7)

            # --- equivalence: same stream through both, bit-for-bit ---
            vec = EmbeddingCache(table, cc)
            ref = ReferenceEmbeddingCache(table, cc)
            for ids in stream:
                o_vec, s_vec = vec.lookup(ids)
                o_ref, s_ref = ref.lookup(ids)
                o_vec, o_ref = np.asarray(o_vec), np.asarray(o_ref)
                assert (o_vec == table[np.asarray(ids, np.int64)]).all(), \
                    "vectorized lookup output differs from table[ids]"
                assert (o_vec == o_ref).all(), "vectorized != reference rows"
                assert s_vec == s_ref, f"stats drift: {s_vec} != {s_ref}"
            for attr in ("_slot_id", "_slot_rrpv", "_slot_ts", "_id_slot"):
                assert (getattr(vec, attr) == getattr(ref, attr)).all(), \
                    f"cold-region metadata drift in {attr}"
            for key in ("hot_hits", "cold_hits", "misses", "bypassed"):
                cv = vec.metrics.counters.get(key, 0)
                cr = ref.metrics.counters.get(key, 0)
                assert cv == cr, f"counter {key} drift: {cv} != {cr}"
            vec.check_consistency()
            # ServeMetrics semantics: can go negative under heavy
            # thrashing (same-batch fills displaced again count as misses)
            hit_rate = vec.metrics.hit_rate
            assert hit_rate == ref.metrics.hit_rate, "hit-rate drift"

            # --- timing: fresh caches, short warmup, full stream ------
            rates = {}
            for name, cls in (("vectorized", EmbeddingCache),
                              ("reference", ReferenceEmbeddingCache)):
                cache = cls(table, cc)
                for ids in stream[:5]:
                    cache.lookup(ids)
                t0 = time.perf_counter()
                for ids in stream:
                    cache.lookup(ids)
                dt = time.perf_counter() - t0
                rates[name] = batch * len(stream) / dt
            speedup = rates["vectorized"] / rates["reference"]
            results[f"{skew}_b{batch}"] = {
                "batch": batch,
                "skew": skew,
                "rows_per_s_vectorized": rates["vectorized"],
                "rows_per_s_reference": rates["reference"],
                "speedup": speedup,
                "hit_rate": hit_rate,
            }
            print(f"[perf-smoke] lookup {skew:9s} b={batch:5d}: "
                  f"vec={rates['vectorized']:>10.0f} rows/s "
                  f"ref={rates['reference']:>8.0f} rows/s "
                  f"({speedup:6.1f}x, hit={hit_rate:.2%})")

    accept = results[f"{ACCEPT_SKEW}_b{ACCEPT_BATCH}"]
    assert accept["speedup"] >= ACCEPT_SPEEDUP, (
        f"vectorized lookup must be >= {ACCEPT_SPEEDUP}x the reference at "
        f"batch {ACCEPT_BATCH} on {ACCEPT_SKEW} "
        f"(got {accept['speedup']:.2f}x)")
    return results


def bench_dist(steps: int = 5):
    """Pipelined vs sequential GRASP exchange: equal to f32 rounding,
    then timed."""
    import jax
    import jax.numpy as jnp

    if jax.device_count() != 8:
        print("[perf-smoke] dist: skipped (needs 8 host devices)")
        return {"skipped": True}

    from repro.configs import base as cfgs
    from repro.core.reorder import reorder_ranks
    from repro.dist import collectives as coll
    from repro.graph import generate
    from repro.graph.csr import apply_reorder
    from repro.launch.mesh import make_debug_mesh
    from repro.nn import gnn as gnn_mod
    from repro.train import optimizer as opt_mod

    P, n_layers = 8, 3
    mesh = make_debug_mesh(2, 4)
    g = generate.rmat(10, 8, seed=3)
    g = apply_reorder(g, reorder_ranks(g, "dbg"))
    spec = coll.partition_spec_for(g.num_nodes, g.num_edges, P, hot=256,
                                   pub_frac=1.0, edge_slack=3.0)
    part = coll.grasp_partition(g, spec)
    assert part["dropped"] == 0

    cfg = cfgs.GNNConfig(name="perf", kind="gin", n_layers=n_layers,
                         d_hidden=32)
    d_feat, n_classes = 16, 5
    rng = np.random.default_rng(0)
    params0 = gnn_mod.init(jax.random.PRNGKey(0), cfg, d_feat=d_feat)
    opt_init, opt_update = opt_mod.make(opt_mod.OptConfig(lr=1e-3))

    x = rng.standard_normal((spec.num_nodes, d_feat)).astype(np.float32)
    labels = rng.integers(0, n_classes, spec.num_nodes).astype(np.int32)
    lab_own = np.zeros((P, spec.n_own), np.int32)
    for p in range(P):
        hot_ids = np.arange(p * spec.hot_per_dev, (p + 1) * spec.hot_per_dev)
        cold_ids = spec.hot + np.arange(p * spec.cold_per_dev,
                                        (p + 1) * spec.cold_per_dev)
        lab_own[p] = labels[np.concatenate([hot_ids, cold_ids])]
    batch = dict(
        x_hot=jnp.asarray(x[:spec.hot]),
        x_cold=jnp.asarray(x[spec.hot:].reshape(P, spec.cold_per_dev, d_feat)),
        esrc=jnp.asarray(part["esrc"]), edst=jnp.asarray(part["edst"]),
        emask=jnp.asarray(part["emask"]), pub=jnp.asarray(part["pub"]),
        labels=jnp.asarray(lab_own))

    out = {"num_nodes": int(spec.num_nodes), "num_edges": int(g.num_edges),
           "layers": n_layers, "steps": steps, "devices": P,
           "collectives_per_step": {"sequential": 2 * n_layers,
                                    "pipelined": n_layers}}
    traj, final_params = {}, {}
    for name, overlap in (("sequential", False), ("pipelined", True)):
        step, _ = coll.make_grasp_gin_step(spec, cfg, d_feat, n_classes,
                                           mesh, opt_update, overlap=overlap)
        p_, o_ = params0, opt_init(params0)
        losses = []
        with jax.set_mesh(mesh):
            jstep = jax.jit(step)
            p_, o_, m = jstep(p_, o_, batch)        # compile + step 1
            losses.append(float(m["loss"]))
            t0 = time.perf_counter()
            for _ in range(steps - 1):
                p_, o_, m = jstep(p_, o_, batch)
                losses.append(float(m["loss"]))
            jax.block_until_ready(p_)
            dt = time.perf_counter() - t0
        traj[name] = losses
        final_params[name] = p_
        out[name] = {"step_ms": dt / max(steps - 1, 1) * 1e3,
                     "losses": losses}
        print(f"[perf-smoke] dist {name:10s}: "
              f"{out[name]['step_ms']:7.1f} ms/step  loss[0]={losses[0]:.6f}")

    np.testing.assert_allclose(
        traj["pipelined"], traj["sequential"], rtol=1e-6, atol=0,
        err_msg="pipelined GRASP step loss diverged from sequential")
    leaves_s = jax.tree_util.tree_leaves(final_params["sequential"])
    leaves_p = jax.tree_util.tree_leaves(final_params["pipelined"])
    for a, b in zip(leaves_s, leaves_p):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=0, atol=1e-6,
            err_msg="pipelined GRASP step params diverged from sequential")
    out["matches_within_1e-6"] = True
    out["speedup"] = (out["sequential"]["step_ms"]
                      / out["pipelined"]["step_ms"])
    return out


def bench_hot_gather(iters: int = 10):
    """Pinned-hot-region Pallas gather microbench (interpret on CPU)."""
    import jax.numpy as jnp

    from repro import kernels
    from repro.kernels.hot_gather.hot_gather import hot_gather_hot_part

    hot, d, e, tile = 512, 128, 4096, 1024
    rng = np.random.default_rng(0)
    table = rng.standard_normal((hot, d)).astype(np.float32)
    idx = rng.integers(-1, hot, e).astype(np.int32)   # -1 = cold fixup rows
    table_j, idx_j = jnp.asarray(table), jnp.asarray(idx)

    rows = np.asarray(hot_gather_hot_part(table_j, idx_j, tile_e=tile))
    want = np.where((idx >= 0)[:, None], table[np.clip(idx, 0, hot - 1)], 0.0)
    assert (rows == want).all(), "hot_gather kernel != dense reference gather"

    t0 = time.perf_counter()
    for _ in range(iters):
        hot_gather_hot_part(table_j, idx_j, tile_e=tile).block_until_ready()
    dt = time.perf_counter() - t0
    out = {"hot_rows": hot, "dim": d, "idx_len": e, "tile_e": tile,
           "interpret": kernels.interpret(), "rows_per_s": e * iters / dt}
    print(f"[perf-smoke] hot_gather (interpret): "
          f"{out['rows_per_s']:.0f} rows/s over {iters} iters")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_perf.json")
    ap.add_argument("--dist-steps", type=int, default=5)
    args = ap.parse_args(argv)

    lookup = bench_lookup()
    dist = bench_dist(steps=args.dist_steps)
    hot_gather = bench_hot_gather()

    accept = lookup[f"{ACCEPT_SKEW}_b{ACCEPT_BATCH}"]
    out = {
        "lookup": lookup,
        "dist": dist,
        "hot_gather": hot_gather,
        "verdict": {
            "lookup_speedup_at_accept": accept["speedup"],
            "lookup_accept_floor": ACCEPT_SPEEDUP,
            "dist_matches_within_1e-6": dist.get("matches_within_1e-6"),
            "dist_speedup": dist.get("speedup", None),
        },
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"[perf-smoke] OK — lookup {accept['speedup']:.1f}x at "
          f"b{ACCEPT_BATCH}/{ACCEPT_SKEW} (floor {ACCEPT_SPEEDUP}x); "
          f"wrote {args.out}")
    return out


if __name__ == "__main__":
    main()  # assertion failure -> traceback + non-zero exit
