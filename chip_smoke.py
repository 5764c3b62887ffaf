"""Bring-up check: the repository's main paths on a TPU, against plain
references.

    python chip_smoke.py              # one chip: graph phase, serve phase
    python chip_smoke.py --chips 4    # four chips: the GRASP GIN step only

Phases, run in order in this one process (the chip belongs to one process):

  graph   Graph500/GAP Kronecker graph (a=.57, b=.19, c=.19, edge factor
          16) at scale 22, DBG-reordered; ``apps.pagerank`` on the in-CSR
          and ``apps.sssp`` from vertex 0 on the weighted out-CSR, checked
          against float64 host references (scipy power iteration and
          Dijkstra) that share no code with the apps.
  serve   MIND at its published widths behind the real ``GatewayServer``
          on loopback: 64 ``/v1/score`` requests with zipf(1.1) ids and 32
          candidates each through ``GatewayClient``, the GRASP cache's hot
          block pinned at the largest size the kernel's VMEM allows.
          Every response must be 200 and match a float64 host MIND
          reference (routing, MLP and scoring in numpy, no ``repro.nn``
          code); the cache's rows must equal the table's bit for bit.
  grasp   (``--chips 4`` only) ``make_grasp_gin_step`` for gin-tu at
          ogb_products widths on a 2x2 mesh, 3 training steps, against the
          unpartitioned reference step on one of the same chips.

Each phase prints one line per check: what ran, its sizes, its wall time
(a bring-up time that includes compilation, not a benchmark) and the check
against its reference. Any failed check, any non-200 response, or a
platform other than TPU exits non-zero without the final line. On success
the last line is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it. All data comes from ``--seed``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GRAPH_SCALE = 22          # Graph500 scale: 2^22 vertices, 2^26 edges made
EDGE_FACTOR = 16          # Graph500 / GAP Kronecker edge factor
PR_DAMPING = 0.85
PR_L1 = 1e-6              # stop when ||r_k - r_{k-1}||_1 <= PR_L1 (both sides)
PR_MAX_ITERS = 300
PR_TOL_L1 = 1e-4          # ||rank - ref||_1: ranks sum to 1
SSSP_RTOL = 1e-5          # float32 path sums vs float64 Dijkstra
SCORE_ATOL = SCORE_RTOL = 1e-3
SERVE_REQUESTS = 64
SERVE_CANDIDATES = 32
SERVE_ZIPF_A = 1.1
SERVE_MAX_BATCH = 8
GRASP_STEPS = 3
GRASP_TOL = 1e-4          # loss per step and params, as the 8-device helper
GRASP_SCALES = (20, 19)   # tried largest first: the first whose reference
                          # step fits one chip's memory is run


def log(msg: str) -> None:
    print(msg, flush=True)


def _verdict(ok: bool) -> str:
    return "PASS" if ok else "FAIL"


class CompileCounter:
    """Counts backend compiles and persistent compile-cache hits while it
    is entered."""

    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# graph phase
# ---------------------------------------------------------------------------
def pagerank_reference(indptr, indices, n, damping, l1, max_iters):
    """Float64 power iteration on the in-CSR; dangling mass is spread
    uniformly; stops on the same L1 rule as the app."""
    import scipy.sparse as sp

    a = sp.csr_matrix((np.ones(indices.size), indices, indptr), shape=(n, n))
    out_deg = np.bincount(indices, minlength=n).astype(np.float64)
    dangling = out_deg == 0
    inv_deg = 1.0 / np.maximum(out_deg, 1.0)
    rank = np.full(n, 1.0 / n)
    for it in range(1, max_iters + 1):
        new = (1.0 - damping) / n + damping * (
            a @ (rank * inv_deg) + rank[dangling].sum() / n)
        err = np.abs(new - rank).sum()
        rank = new
        if err <= l1:
            break
    return rank, it


def sssp_reference(g_out, source):
    """Float64 Dijkstra on the weighted out-CSR (row = edge source)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    n = g_out.num_nodes
    m = sp.csr_matrix((g_out.weights.astype(np.float64), g_out.indices,
                       g_out.indptr), shape=(n, n))
    return dijkstra(m, directed=True, indices=source)


def graph_phase(scale: int, seed: int) -> list:
    import jax

    from repro.apps.pagerank import pagerank
    from repro.apps.sssp import sssp
    from repro.core.reorder import reorder_ranks
    from repro.graph import generate
    from repro.graph.csr import apply_reorder, transpose

    t0 = time.perf_counter()
    g = generate.rmat(scale, EDGE_FACTOR, seed=seed)
    g = apply_reorder(g, reorder_ranks(g, "dbg"))
    g = generate.add_uniform_weights(g, seed=seed)
    g_out = transpose(g)
    n, m = g.num_nodes, g.num_edges
    log(f"[graph] kronecker scale={scale} edge_factor={EDGE_FACTOR} "
        f"vertices={n} edges={m} (of {n * EDGE_FACTOR} generated; "
        f"duplicates and self-loops dropped), DBG-reordered; host build "
        f"{time.perf_counter() - t0:.1f} s")
    checks = []

    # --- PageRank (pull, in-CSR) ---
    t0 = time.perf_counter()
    dg = g.device()
    rank = np.asarray(jax.block_until_ready(pagerank(
        dg, damping=PR_DAMPING, tol=PR_L1 / n, max_iters=PR_MAX_ITERS)))
    dt = time.perf_counter() - t0
    del dg
    t1 = time.perf_counter()
    ref, ref_iters = pagerank_reference(g.indptr, g.indices, n, PR_DAMPING,
                                        PR_L1, PR_MAX_ITERS)
    l1 = float(np.abs(rank.astype(np.float64) - ref).sum())
    ok = bool(np.isfinite(rank).all()) and l1 <= PR_TOL_L1
    log(f"[graph] pagerank damping={PR_DAMPING} stop L1<={PR_L1}: bring-up "
        f"wall {dt:.2f} s incl. compile (not a benchmark); float64 reference "
        f"{ref_iters} iters in {time.perf_counter() - t1:.1f} s; "
        f"||rank-ref||_1={l1:.3e} (tol {PR_TOL_L1}) {_verdict(ok)}")
    checks.append(("pagerank", ok))

    # --- SSSP (push, weighted out-CSR) from vertex 0 ---
    t0 = time.perf_counter()
    dg_out = g_out.device()
    dist = np.asarray(jax.block_until_ready(sssp(dg_out, 0)))
    dt = time.perf_counter() - t0
    del dg_out
    t1 = time.perf_counter()
    ref = sssp_reference(g_out, 0)
    reach, ref_reach = np.isfinite(dist), np.isfinite(ref)
    same_reach = bool((reach == ref_reach).all())
    rel = float((np.abs(dist[reach] - ref[reach])
                 / np.maximum(ref[reach], 1.0)).max()) if reach.any() else 0.0
    ok = same_reach and reach.sum() > 1 and rel <= SSSP_RTOL
    log(f"[graph] sssp source=0 weights U[1,64): bring-up wall {dt:.2f} s "
        f"incl. compile (not a benchmark); Dijkstra reference "
        f"{time.perf_counter() - t1:.1f} s; reached {int(reach.sum())}/{n} "
        f"(same set as reference: {same_reach}); max rel err {rel:.3e} "
        f"(tol {SSSP_RTOL}) {_verdict(ok)}")
    checks.append(("sssp", ok))
    return checks


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------
def mind_scores_reference(params, cfg, hist, cand):
    """Float64 MIND serving scores on the host, sharing no code with
    ``repro.nn``: history rows through the bilinear map, B2I routing
    (logits initialised to sin(id * (k + 1)), softmax over interests,
    squash) for ``capsule_iters`` rounds, the two-layer ReLU interest MLP
    added back, then the max over interests of each candidate's dot
    product. Every history slot is valid, as in the requests sent."""
    items = np.asarray(params["items"])
    e = items[hist].astype(np.float64)                            # (B, H, d)
    eh = e @ np.asarray(params["s_mat"], np.float64)
    logits = np.sin(hist[..., None].astype(np.float64)
                    * np.arange(1, cfg.n_interests + 1))          # (B, H, K)
    for _ in range(cfg.capsule_iters):
        w = np.exp(logits - logits.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        z = np.einsum("bhk,bhd->bkd", w, eh)
        n2 = (z * z).sum(-1, keepdims=True)
        interests = n2 / (1.0 + n2) * z / np.sqrt(n2 + 1e-9)
        logits = logits + np.einsum("bkd,bhd->bhk", interests, eh)
    w0, w1 = (np.asarray(layer["w"], np.float64) for layer in params["mlp"])
    interests = interests + np.maximum(interests @ w0, 0.0) @ w1
    cand_e = items[cand].astype(np.float64)                       # (B, C, d)
    return np.einsum("bkd,bcd->bkc", interests, cand_e).max(axis=1)


def serve_phase(cfg, seed: int, counter: CompileCounter) -> list:
    import jax
    import jax.numpy as jnp

    from repro import kernels
    from repro.core import plan as plan_mod
    from repro.data.pipeline import zipf_ids
    from repro.gateway import EnginePump, GatewayClient, GatewayServer
    from repro.kernels.hot_gather.hot_gather import IDX_TILE, hot_gather_hot_part
    from repro.nn import recsys
    from repro.serve.cache import LANE, CacheConfig
    from repro.serve.engine import RecsysServeEngine
    from repro.serve.scheduler import SchedulerConfig

    t0 = time.perf_counter()
    params = recsys.init(jax.random.PRNGKey(seed), cfg)
    d_pad = (cfg.embed_dim + LANE - 1) // LANE * LANE
    cap = plan_mod.kernel_hot_rows(d_pad * 4, IDX_TILE)
    row_bytes = cfg.embed_dim * 4
    # half the budget pinned, asking for the kernel cap: the cache then
    # pins min(cap, table), with as many flexible cold slots again
    cache_cfg = CacheConfig(budget_bytes=2 * cap * row_bytes,
                            hot_fraction=0.5)
    engine = RecsysServeEngine(params, cfg, cache_cfg,
                               SchedulerConfig(max_batch=SERVE_MAX_BATCH,
                                               max_queue=4 * SERVE_REQUESTS))
    cache = engine.cache
    engine.warmup(SERVE_CANDIDATES)
    # compile the cache's kernel once outside the request path, and show
    # what it compiled to
    idx = jnp.full((IDX_TILE,), -1, jnp.int32)
    args, kw = (cache._hot_block, idx), {"tile_e": cache_cfg.tile_e}
    text = hot_gather_hot_part.lower(*args, **kw).compile().as_text()
    jax.block_until_ready(hot_gather_hot_part(*args, **kw))
    compiled_kernel = "tpu_custom_call" in text
    kernel_ok = compiled_kernel == (not kernels.interpret())
    log(f"[serve] mind items={cfg.n_items} dim={cfg.embed_dim} "
        f"hist={cfg.hist_len} interests={cfg.n_interests} "
        f"capsule_iters={cfg.capsule_iters} d_hidden={cfg.d_hidden}; cache "
        f"hot rows={cache.hot_size} (kernel VMEM cap {cap} rows at "
        f"{d_pad} lanes) cold slots={cache.cold_slots}; kernel "
        f"{'compiled (tpu_custom_call)' if compiled_kernel else 'interpreted'}"
        f" {_verdict(kernel_ok)}; set-up {time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(seed)
    hist = zipf_ids(rng, (SERVE_REQUESTS, cfg.hist_len), cfg.n_items,
                    a=SERVE_ZIPF_A)
    cand = zipf_ids(rng, (SERVE_REQUESTS, SERVE_CANDIDATES), cfg.n_items,
                    a=SERVE_ZIPF_A)

    server = GatewayServer({"score": EnginePump(engine, "score")},
                           host="127.0.0.1", port=0,
                           request_timeout_s=600.0).start()
    try:
        # no client retries: every non-200 surfaces and is counted
        client = GatewayClient(server.url, timeout_s=600.0, retries=0)
        compiles0 = counter.compiles
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(SERVE_MAX_BATCH) as pool:
            futs = [pool.submit(client.score, hist[i], cand[i])
                    for i in range(SERVE_REQUESTS)]
            results = []
            for f in futs:
                try:
                    results.append(f.result())
                except Exception as e:  # noqa: BLE001 — counted, not hidden
                    results.append(e)
        dt = time.perf_counter() - t0
        compiles = counter.compiles - compiles0
    finally:
        server.stop()

    n_ok = sum(not isinstance(r, Exception) for r in results)
    errors = sorted({type(r).__name__ for r in results
                     if isinstance(r, Exception)})
    stats = engine.metrics.snapshot()
    log(f"[serve] {SERVE_REQUESTS} /v1/score requests ({SERVE_CANDIDATES} "
        f"candidates, zipf a={SERVE_ZIPF_A}) over loopback: {n_ok} answered "
        f"200 {errors if errors else ''}; cache hit rate "
        f"{stats['hit_rate']:.4f}; compiles during requests: {compiles}; "
        f"bring-up wall {dt:.2f} s (not a benchmark)")
    checks = [("serve_kernel", kernel_ok),
              ("serve_200", n_ok == SERVE_REQUESTS)]

    t0 = time.perf_counter()
    ref = mind_scores_reference(params, cfg, hist, cand)
    worst = 0.0
    for i, r in enumerate(results):
        if not isinstance(r, Exception):
            worst = max(worst, float(np.max(
                np.abs(r - ref[i]) / (SCORE_ATOL + SCORE_RTOL * np.abs(ref[i])))))
    ok = n_ok == SERVE_REQUESTS and worst <= 1.0
    log(f"[serve] scores vs float64 host MIND reference "
        f"({time.perf_counter() - t0:.1f} s): worst |got-ref|/"
        f"({SCORE_ATOL}+{SCORE_RTOL}|ref|)={worst:.3e} (must be <= 1) "
        f"{_verdict(ok)}")
    checks.append(("serve_scores", ok))

    # the cache changes where rows come from, never their values
    ids = np.concatenate([hist.ravel(), cand.ravel()])[:4 * IDX_TILE]
    rows, _ = cache.lookup(ids)
    exact = bool((np.asarray(rows) == cache.table[ids]).all())
    log(f"[serve] cache.lookup of {ids.size} request ids == table rows bit "
        f"for bit: {exact} {_verdict(exact)}")
    checks.append(("serve_rows", exact))
    return checks


# ---------------------------------------------------------------------------
# grasp phase (four chips)
# ---------------------------------------------------------------------------
def _ref_step_fn(cfg, opt_update):
    import jax

    from repro.launch.steps import _gnn_loss

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(_gnn_loss)(params, cfg, batch)
        new_params, new_opt = opt_update(grads, opt_state, params)
        return new_params, new_opt, loss

    return step


def _compile_if_fits(lowered, limit):
    """(compiled or None, verdict). The compiler itself refuses a program
    that cannot fit the device; otherwise ``memory_analysis`` decides."""
    try:
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 — only a refusal for size is a verdict
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        used = re.search(r"Used [\d.]+\w* of [\d.]+\w* \w+", str(e))
        return None, f"refused by the compiler ({used.group(0) if used else e})"
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    if limit is not None and need > limit:
        return None, f"needs {need / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB"
    of = "" if limit is None else f" of {limit / 2**30:.2f} GiB"
    return compiled, f"fits: needs {need / 2**30:.2f} GiB{of}"


def grasp_phase(devices, seed: int, scales=GRASP_SCALES) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.configs import base as cfgs
    from repro.core.reorder import reorder_ranks
    from repro.dist import collectives as coll
    from repro.dist import sharding as shd
    from repro.graph import generate
    from repro.graph.csr import apply_reorder
    from repro.nn import gnn as gnn_mod
    from repro.train import optimizer as opt_mod

    cfg = cfgs.get_arch("gin-tu")
    shape = cfgs.GNN_SHAPES["ogb_products"]
    d_feat = shape.d_feat
    avg_degree = round(shape.n_edges / shape.n_nodes)
    n_dev = len(devices)
    opt_init, opt_update = opt_mod.make(opt_mod.OptConfig(name="adamw",
                                                          lr=1e-3))
    ref_dev = devices[0]
    one = SingleDeviceSharding(ref_dev)
    stats = ref_dev.memory_stats()
    limit = stats.get("bytes_limit") if stats else None

    def sds(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one)

    a_params = jax.eval_shape(lambda k: gnn_mod.init(k, cfg, d_feat),
                              jax.random.PRNGKey(0))
    a_params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                      a_params)
    a_opt = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                   jax.eval_shape(opt_init, a_params))
    ref_step = jax.jit(_ref_step_fn(cfg, opt_update))

    # --- the largest scale whose unpartitioned reference step fits ---
    chosen = None
    for scale in sorted(scales, reverse=True):
        n = 1 << scale
        spec = coll.partition_spec_for(n, n * avg_degree, n_dev,
                                       elem_bytes=d_feat * 4)
        n_pad, e_cap = spec.num_nodes, n * avg_degree
        a_batch = {"x": sds((n_pad, d_feat), jnp.float32),
                   "src": sds((e_cap,), jnp.int32),
                   "dst": sds((e_cap,), jnp.int32),
                   "emask": sds((e_cap,), jnp.bool_),
                   "labels": sds((n_pad,), jnp.int32)}
        t0 = time.perf_counter()
        compiled, verdict = _compile_if_fits(
            ref_step.lower(a_params, a_opt, a_batch), limit)
        log(f"[grasp] reference step at scale {scale} ({n_pad} nodes, "
            f"{e_cap} edge slots) on one chip: {verdict} (compile "
            f"{time.perf_counter() - t0:.1f} s)")
        if compiled is not None:
            chosen = (scale, compiled, e_cap)
            break
    if chosen is None:
        log("[grasp] no scale fits one chip FAIL")
        return [("grasp_fit", False)]
    scale, ref_compiled, e_cap = chosen

    # --- graph, partition, data ---
    t0 = time.perf_counter()
    g = generate.rmat(scale, avg_degree, seed=seed)
    g = apply_reorder(g, reorder_ranks(g, "dbg"))
    n, m = g.num_nodes, g.num_edges
    # first pass: room for every edge on every device; second pass: the
    # tightest per-device edge table that still drops nothing
    spec = coll.partition_spec_for(n, m, n_dev, elem_bytes=d_feat * 4,
                                   pub_frac=1.0, edge_slack=float(n_dev))
    busiest = int(coll.grasp_partition(g, spec)["emask"].sum(1).max())
    spec = coll.partition_spec_for(n, m, n_dev, elem_bytes=d_feat * 4,
                                   pub_frac=1.0,
                                   edge_slack=busiest * n_dev / m)
    part = coll.grasp_partition(g, spec)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((spec.num_nodes, d_feat)).astype(np.float32)
    labels = rng.integers(0, cfg.d_out, spec.num_nodes).astype(np.int32)
    params0 = gnn_mod.init(jax.random.PRNGKey(seed), cfg, d_feat)
    params0 = jax.tree_util.tree_map(np.asarray, params0)
    log(f"[grasp] gin-tu layers={cfg.n_layers} d_hidden={cfg.d_hidden} "
        f"d_feat={d_feat}; rmat scale={scale} avg_degree={avg_degree}: "
        f"{n} nodes, {m} edges; {n_dev}-way GRASP partition hot={spec.hot} "
        f"c_pub={spec.c_pub} e_loc={spec.e_loc} dropped={part['dropped']}; "
        f"host build {time.perf_counter() - t0:.1f} s")
    checks = [("grasp_no_drop", part["dropped"] == 0)]

    # --- GRASP step over the mesh ---
    mesh_shape = (2, n_dev // 2) if n_dev % 2 == 0 else (1, n_dev)
    mesh = jax.make_mesh(mesh_shape, ("data", "model"), devices=devices)
    step, batch_specs = coll.make_grasp_gin_step(
        spec, cfg, d_feat, cfg.d_out, mesh, opt_update)
    hpd, cpd = spec.hot_per_dev, spec.cold_per_dev
    host_batch = {
        "x_hot": x[:spec.hot],
        "x_cold": x[spec.hot:].reshape(n_dev, cpd, d_feat),
        "esrc": part["esrc"], "edst": part["edst"], "emask": part["emask"],
        "pub": part["pub"],
        "labels": np.concatenate([labels[:spec.hot].reshape(n_dev, hpd),
                                  labels[spec.hot:].reshape(n_dev, cpd)],
                                 axis=1),
    }
    # numpy straight to its shards: nothing is staged on the first device
    batch = {k: jax.device_put(v, shd.ns(mesh, *batch_specs[k]))
             for k, v in host_batch.items()}
    p_ = jax.device_put(params0, shd.ns(mesh))
    o_ = jax.device_put(opt_init(params0), shd.ns(mesh))
    spread = {k: len({s.device for s in v.addressable_shards})
              for k, v in batch.items()}
    t0 = time.perf_counter()
    jstep = jax.jit(step)
    grasp_losses = []
    for _ in range(GRASP_STEPS):
        p_, o_, met = jstep(p_, o_, batch)
        grasp_losses.append(float(met["loss"]))
    grasp_params = jax.tree_util.tree_map(np.asarray, p_)
    dt = time.perf_counter() - t0
    sharded_ok = all(spread[k] == n_dev for k in batch_specs
                     if batch_specs[k])
    log(f"[grasp] {GRASP_STEPS} steps on a {mesh_shape[0]}x{mesh_shape[1]} mesh: "
        f"bring-up wall {dt:.2f} s incl. compile (not a benchmark); "
        f"devices holding shards {spread} {_verdict(sharded_ok)}")
    checks.append(("grasp_sharded", sharded_ok))
    del batch, p_, o_, met

    # --- unpartitioned reference on one of the same chips ---
    src = np.zeros(e_cap, np.int32)
    dst = np.zeros(e_cap, np.int32)
    emask = np.zeros(e_cap, bool)
    src[:m], dst[:m], emask[:m] = g.indices, g.dst_ids(), True
    ref_batch = jax.device_put({"x": x, "src": src, "dst": dst,
                                "emask": emask, "labels": labels}, one)
    rp, ro = jax.device_put((params0, opt_init(params0)), one)
    t0 = time.perf_counter()
    ref_losses = []
    for _ in range(GRASP_STEPS):
        rp, ro, loss = ref_compiled(rp, ro, ref_batch)
        ref_losses.append(float(loss))
    ref_params = jax.tree_util.tree_map(np.asarray, rp)
    dt = time.perf_counter() - t0
    loss_diff = max(abs(a - b) for a, b in zip(grasp_losses, ref_losses))
    diffs = {jax.tree_util.keystr(path): float(np.abs(a - b).max())
             for (path, a), b in zip(
                 jax.tree_util.tree_flatten_with_path(grasp_params)[0],
                 jax.tree_util.tree_leaves(ref_params))}
    worst_leaf = max(diffs, key=diffs.get)
    param_diff = diffs[worst_leaf]
    ok = loss_diff <= GRASP_TOL and param_diff <= GRASP_TOL
    log(f"[grasp] reference step on {ref_dev.device_kind} id={ref_dev.id}: "
        f"bring-up wall {dt:.2f} s; losses grasp="
        f"{[f'{v:.7f}' for v in grasp_losses]} ref="
        f"{[f'{v:.7f}' for v in ref_losses]}; max |loss diff|="
        f"{loss_diff:.3e}, max |param diff|={param_diff:.3e} at "
        f"{worst_leaf} (tol {GRASP_TOL}) {_verdict(ok)}")
    checks.append(("grasp_matches_reference", ok))
    return checks


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the GRASP GIN step across four chips")
    args = ap.parse_args(argv)

    import jax

    from repro.launch import compile_cache

    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; jax {jax.__version__}")
    if dev.platform != "tpu":
        log(f"[device] platform {dev.platform!r} is not a TPU FAIL")
        return 1
    if len(devices) < args.chips:
        log(f"[device] {args.chips} chips asked, {len(devices)} found FAIL")
        return 1
    log(f"[device] compile cache {compile_cache.enable()}")

    with CompileCounter() as counter:
        if args.chips == 4:
            checks = grasp_phase(devices[:4], args.seed)
        else:
            from repro.configs import base as cfgs

            checks = graph_phase(GRAPH_SCALE, args.seed)
            checks += serve_phase(cfgs.get_arch("mind"), args.seed, counter)
    failed = [name for name, ok in checks if not ok]
    log(f"[summary] {len(checks) - len(failed)}/{len(checks)} checks passed"
        f"{'; failed: ' + ', '.join(failed) if failed else ''}; "
        f"{counter.compiles} compiles, {counter.cache_hits} read from the "
        f"persistent compile cache")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
