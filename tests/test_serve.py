"""repro.serve: GRASP embedding cache, continuous-batching scheduler,
metrics, and the serving engines.

The cache tests all pivot on one invariant: whatever the region geometry
or eviction pressure, ``lookup(ids)`` returns exactly ``table[ids]`` — the
cache moves rows, never values.
"""
import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.serve.cache import CacheConfig, EmbeddingCache, LookupStats
from repro.serve.metrics import ServeMetrics
from repro.serve.refcache import ReferenceEmbeddingCache
from repro.serve.scheduler import (
    ContinuousBatcher,
    SchedulerConfig,
    VirtualClock,
)

N, D = 512, 8
ROW = D * 4


def _table(n=N, d=D, seed=0):
    return np.random.default_rng(seed).standard_normal((n, d)).astype(np.float32)


def _cache(table, rows, hot_fraction=0.5, **kw):
    cc = CacheConfig(budget_bytes=rows * table.shape[1] * 4,
                     hot_fraction=hot_fraction, tile_e=128, **kw)
    return EmbeddingCache(table, cc)


def _ref_check(cache, table, ids):
    out, stats = cache.lookup(ids)
    np.testing.assert_array_equal(np.asarray(out), table[np.asarray(ids)])
    cache.check_consistency()
    return stats


# ---------------------------------------------------------------------------
# sizing
# ---------------------------------------------------------------------------
def test_entries_for_budget():
    assert plan_mod.entries_for_budget(1024, 32) == 32
    assert plan_mod.entries_for_budget(1024, 32, align=5) == 30
    assert plan_mod.entries_for_budget(1 << 30, 32, max_entries=100) == 100
    assert plan_mod.entries_for_budget(0, 32) == 0
    assert plan_mod.entries_for_budget(31, 32) == 0


def test_partition_spec_budget_sizing():
    """dist hot-replica sizing now derives from a byte budget (ROADMAP)."""
    from repro.dist import collectives as coll

    spec = coll.partition_spec_for(10_000, 50_000, 4,
                                   hot_budget_bytes=1000 * 16, elem_bytes=16)
    assert spec.hot == 1000  # 1000 rows afforded; already a multiple of 4
    # explicit hot still wins (test/ablation path)
    assert coll.partition_spec_for(10_000, 50_000, 4, hot=64).hot == 64
    # default budget (64 MiB) clamps to the graph
    assert coll.partition_spec_for(100, 400, 4).hot == 100


def test_cache_regions_sized_from_bytes():
    table = _table()
    c = _cache(table, rows=64, hot_fraction=0.5)
    assert c.capacity == 64 and c.hot_size == 32 and c.cold_slots == 32
    assert c.pin_ratio == pytest.approx(0.5)
    # degree stats cap the pinned region at the true hot-vertex count
    degree = np.zeros(N)
    degree[:10] = 100.0  # only 10 vertices are >= average degree
    cc = CacheConfig(budget_bytes=64 * ROW, hot_fraction=0.5, tile_e=128)
    c2 = EmbeddingCache(table, cc, degree=degree)
    assert c2.hot_size == 10 and c2.capacity == 64 and c2.cold_slots == 54


# ---------------------------------------------------------------------------
# eviction edge cases (ISSUE satellite)
# ---------------------------------------------------------------------------
def test_cold_start_fill_matches_dense_gather():
    table = _table()
    c = _cache(table, rows=N)          # hot 256 + cold 256: working set fits
    rng = np.random.default_rng(1)
    ids = rng.integers(0, N, 100)
    st = _ref_check(c, table, ids)     # empty cache: every unique cold fills
    uniq_cold = np.unique(ids[ids >= c.hot_size]).size
    assert st.misses == uniq_cold and st.bypassed == 0
    st2 = _ref_check(c, table, ids)    # same batch again: all hits
    assert st2.misses == 0 and st2.hit_rate == 1.0


def test_hot_region_larger_than_table():
    table = _table()
    c = _cache(table, rows=4 * N, hot_fraction=1.0)  # budget >> table
    assert c.hot_size == N and c.cold_slots == 0
    st = _ref_check(c, table, np.arange(N))
    assert st.hot_hits == N and st.misses == 0


def test_zero_capacity_cold_region():
    table = _table()
    c = _cache(table, rows=32, hot_fraction=1.0)     # all budget pinned
    assert c.hot_size == 32 and c.cold_slots == 0
    ids = np.array([0, 1, 31, 32, 100, 100, N - 1])
    st = _ref_check(c, table, ids)
    assert st.hot_hits == 3
    # cold refs can never be cached: every one is a bypassed miss
    assert st.misses == 4 and st.bypassed == 4
    st2 = _ref_check(c, table, ids)
    assert st2.misses == 4  # still — nothing was retained


def test_duplicate_ids_within_one_batch():
    table = _table()
    c = _cache(table, rows=32, hot_fraction=0.5)
    rid = c.hot_size + 7
    ids = np.array([rid] * 5 + [3] * 2)              # 5 cold dups + 2 hot dups
    st = _ref_check(c, table, ids)
    assert st.hot_hits == 2
    assert st.misses == 1                            # one fill serves all dups
    assert st.cold_hits == 4


def test_eviction_under_pressure_keeps_correctness():
    """Working set far beyond capacity, many batches; residency stays
    bounded and every answer matches the dense gather."""
    table = _table()
    c = _cache(table, rows=24, hot_fraction=0.25)    # hot 6 + cold 18
    rng = np.random.default_rng(2)
    for _ in range(10):
        ids = np.minimum(rng.zipf(1.2, 200) - 1, N - 1)
        _ref_check(c, table, ids)
        assert int((c._slot_id >= 0).sum()) <= c.cold_slots


def test_lru_policy_and_no_kernel_path():
    table = _table()
    rng = np.random.default_rng(3)
    for kw in ({"policy": "lru"}, {"use_kernel": False}):
        c = _cache(table, rows=48, **kw)
        for _ in range(4):
            _ref_check(c, table, rng.integers(0, N, 64))


def test_unpinned_baseline_has_no_hot_region():
    c = _cache(_table(), rows=64, hot_fraction=0.0)
    assert c.hot_size == 0 and c.cold_slots == 64 and c.pin_ratio == 0.0


def test_out_of_range_ids_rejected():
    c = _cache(_table(), rows=16)
    with pytest.raises(IndexError):
        c.lookup(np.array([N]))
    with pytest.raises(IndexError):
        c.lookup(np.array([-1]))


def test_empty_lookup_short_circuits():
    """Empty id batches return a (0, d) block and zero-count stats without
    ticking the eviction clock or disturbing residency (ISSUE satellite)."""
    table = _table()
    c = _cache(table, rows=32)
    _ref_check(c, table, np.arange(c.hot_size, c.hot_size + 8))
    clock, resident = c._clock, c._resident
    out, st = c.lookup(np.array([], dtype=np.int64))
    assert np.asarray(out).shape == (0, D)
    assert st == LookupStats()          # all-zero counts
    assert st.hit_rate == 0.0
    assert c._clock == clock and c._resident == resident
    c.check_consistency()
    # still works mid-stream: the next real batch is unaffected
    st2 = _ref_check(c, table, np.arange(c.hot_size, c.hot_size + 8))
    assert st2.misses == 0


def test_vectorized_lookup_matches_reference_loop():
    """The batched eviction/insert path must be bit-identical to the
    retained pre-vectorization loop: same rows, same stats, same
    cold-region metadata, under both policies and heavy thrashing."""
    table = _table()
    for policy in ("rrpv", "lru"):
        for rows, hot_fraction in ((24, 0.25), (32, 0.5), (8, 0.0)):
            cc = CacheConfig(budget_bytes=rows * ROW, hot_fraction=hot_fraction,
                             policy=policy, tile_e=128, use_kernel=False)
            vec = EmbeddingCache(table, cc)
            ref = ReferenceEmbeddingCache(table, cc)
            rng = np.random.default_rng(hash((policy, rows)) % 2**31)
            for bi in range(12):
                if bi == 5:
                    ids = np.array([], dtype=np.int64)   # empty mid-stream
                elif bi % 2:
                    ids = np.minimum(rng.zipf(1.2, 96) - 1, N - 1)
                else:
                    ids = rng.integers(0, N, 96)
                o_v, s_v = vec.lookup(ids)
                o_r, s_r = ref.lookup(ids)
                np.testing.assert_array_equal(np.asarray(o_v), np.asarray(o_r))
                np.testing.assert_array_equal(np.asarray(o_v),
                                              table[np.asarray(ids, np.int64)])
                assert s_v == s_r
            for attr in ("_slot_id", "_slot_rrpv", "_slot_ts", "_id_slot"):
                np.testing.assert_array_equal(getattr(vec, attr),
                                              getattr(ref, attr))
            assert vec.metrics.counters == ref.metrics.counters
            assert vec.metrics.hit_rate == ref.metrics.hit_rate
            vec.check_consistency()
            ref.check_consistency()


def test_resident_counter_tracks_occupancy_incrementally():
    """cold_resident is now an O(1) counter, not a full-capacity scan: it
    must equal the true occupancy after fills, evictions, and restore."""
    table = _table()
    c = _cache(table, rows=24, hot_fraction=0.25)      # hot 6 + cold 18
    assert c._resident == 0
    rng = np.random.default_rng(4)
    for _ in range(6):
        c.lookup(rng.integers(0, N, 64))
        assert c._resident == int((c._slot_id >= 0).sum())
    assert c.metrics.gauges["cold_resident"] == c._resident
    snap = c.snapshot()
    c2 = _cache(table, rows=24, hot_fraction=0.25)
    c2.restore(snap)
    assert c2._resident == int((c2._slot_id >= 0).sum()) == c._resident
    c2.check_consistency()


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------
def test_admission_control_rejects_when_full():
    clock = VirtualClock()
    b = ContinuousBatcher(SchedulerConfig(max_batch=2, max_queue=3), clock)
    reqs = [b.submit({"i": i}) for i in range(5)]
    assert [r.status for r in reqs] == ["queued"] * 3 + ["rejected"] * 2
    assert b.metrics.counters["admitted"] == 3
    assert b.metrics.counters["rejected"] == 2


def test_shed_expired_and_edf_order():
    clock = VirtualClock()
    b = ContinuousBatcher(SchedulerConfig(max_batch=2, max_queue=10), clock)
    late = b.submit("late", deadline_s=0.5)
    soon = b.submit("soon", deadline_s=0.2)
    dead = b.submit("dead", deadline_s=0.05)
    nodl = b.submit("best-effort")
    clock.advance(0.1)                       # "dead" expires
    batch = b.next_batch()
    assert dead.status == "shed"
    # earliest deadline first; best-effort sorts last
    assert [r.payload for r in batch] == ["soon", "late"]
    assert late.status == soon.status == "running"
    batch2 = b.next_batch()
    assert [r.payload for r in batch2] == ["best-effort"]
    assert nodl.status == "running"
    assert b.metrics.counters["shed"] == 1


def test_latency_accounting_virtual_time():
    clock = VirtualClock()
    b = ContinuousBatcher(SchedulerConfig(max_batch=4, max_queue=8), clock)
    b.submit("x")
    clock.advance(0.25)                      # waits 250ms in queue
    batch = b.next_batch()
    clock.advance(0.1)                       # 100ms of service
    b.complete(batch, ["ok"])
    assert batch[0].result == "ok" and batch[0].status == "done"
    snap = b.metrics.snapshot()
    assert snap["latency"]["queue_wait"]["max_s"] == pytest.approx(0.25)
    assert snap["latency"]["service"]["max_s"] == pytest.approx(0.1)
    assert snap["latency"]["e2e"]["max_s"] == pytest.approx(0.35)


# ---------------------------------------------------------------------------
# scheduler concurrency (gateway-facing guarantees)
# ---------------------------------------------------------------------------
def test_concurrent_submit_admits_exactly_max_queue():
    import threading

    Q, threads_n, per_thread = 16, 8, 10
    b = ContinuousBatcher(SchedulerConfig(max_batch=4, max_queue=Q),
                          VirtualClock())
    reqs = []
    lock = threading.Lock()

    def submitter(k):
        mine = [b.submit({"t": k, "i": i}) for i in range(per_thread)]
        with lock:
            reqs.extend(mine)

    ts = [threading.Thread(target=submitter, args=(k,))
          for k in range(threads_n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30.0)
    assert len(reqs) == threads_n * per_thread
    admitted = [r for r in reqs if r.status == "queued"]
    rejected = [r for r in reqs if r.status == "rejected"]
    assert len(admitted) == Q == b.depth
    assert len(rejected) == threads_n * per_thread - Q
    assert b.metrics.counters["admitted"] == Q
    # rejects resolve synchronously: nobody ever blocks on them
    assert all(r.done.is_set() for r in rejected)
    assert not any(r.done.is_set() for r in admitted)


def test_edf_equal_deadlines_stable_arrival_order():
    clock = VirtualClock()
    b = ContinuousBatcher(SchedulerConfig(max_batch=8, max_queue=16), clock)
    # same virtual arrival instant AND same deadline: ties must break by
    # submission order (rid), not dict/sort accidents
    reqs = [b.submit(i, deadline_s=1.0) for i in range(6)]
    batch = b.next_batch()
    assert [r.payload for r in batch] == list(range(6))
    assert [r.rid for r in batch] == [r.rid for r in reqs]


def test_shed_and_completed_requests_resolve_events():
    clock = VirtualClock()
    b = ContinuousBatcher(SchedulerConfig(max_batch=2, max_queue=8), clock)
    doomed = b.submit("doomed", deadline_s=0.01)
    kept = b.submit("kept", deadline_s=10.0)
    assert not doomed.done.is_set() and not kept.done.is_set()
    clock.advance(0.1)
    batch = b.next_batch()
    assert doomed.status == "shed" and doomed.done.is_set()
    assert doomed.wait(0.0) and doomed.finished is not None
    assert not kept.done.is_set()            # running, not terminal
    b.complete(batch, ["ok"])
    assert kept.done.is_set() and kept.result == "ok"


def test_failed_batch_resolves_events_with_error():
    b = ContinuousBatcher(SchedulerConfig(max_batch=4, max_queue=8),
                          VirtualClock())
    reqs = [b.submit(i) for i in range(3)]
    batch = b.next_batch()
    boom = RuntimeError("forward exploded")
    b.fail(batch, boom)
    assert all(r.status == "failed" and r.done.is_set() and r.error is boom
               for r in reqs)
    assert b.metrics.counters["failed"] == 3


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_histogram_percentiles_and_json(tmp_path):
    m = ServeMetrics()
    for v in [0.001] * 98 + [0.5] * 2:
        m.observe("e2e", v)
    p50, p99 = m.hists["e2e"].percentile(50), m.hists["e2e"].percentile(99)
    assert 0.001 <= p50 <= 0.002          # upper-edge estimate, one bucket up
    assert 0.5 <= p99 <= 1.0
    assert m.hists["e2e"].max == pytest.approx(0.5)  # max is exact
    m.count("misses", 3)
    m.count("hot_hits", 7)
    assert m.hit_rate == pytest.approx(0.7)
    out = tmp_path / "snap.json"
    snap = m.write_json(str(out), extra={"tag": "t"})
    import json

    assert json.loads(out.read_text()) == snap
    assert snap["tag"] == "t"


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------
def test_histogram_overflow_bucket_reports_exact_max():
    """Regression: samples past the last finite edge (~134s) used to read
    back the last edge for any percentile landing in the overflow bucket —
    now they fall back to the exact tracked max."""
    from repro.serve.metrics import LatencyHistogram, _EDGES

    h = LatencyHistogram()
    for v in [0.001] * 98 + [200.0, 500.0]:
        h.observe(v)
    # any percentile landing in the overflow bucket reports the exact max
    # (not the ~134s last edge, and not a quantized estimate)
    assert h.percentile(99) == pytest.approx(500.0)
    assert h.percentile(100) == pytest.approx(500.0)
    assert h.percentile(50) <= 0.002          # mid-range unaffected
    # only overflow samples: every percentile reports the exact max
    h2 = LatencyHistogram()
    h2.observe(float(_EDGES[-1]) * 4)
    h2.observe(float(_EDGES[-1]) * 8)
    for p in (50, 99, 100):
        assert h2.percentile(p) == pytest.approx(float(_EDGES[-1]) * 8)


def test_metrics_thread_safe_under_concurrent_mutation():
    import threading

    m = ServeMetrics()
    N, per = 8, 500

    def hammer(k):
        for i in range(per):
            m.count("hot_hits")
            m.observe("e2e", 0.001 * (k + 1))
            m.gauge("last", float(i))

    ts = [threading.Thread(target=hammer, args=(k,)) for k in range(N)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30.0)
    snap = m.snapshot()
    assert snap["counters"]["hot_hits"] == N * per
    assert snap["latency"]["e2e"]["count"] == N * per
    assert snap["latency"]["e2e"]["max_s"] == pytest.approx(0.008)


def test_recsys_engine_matches_dense_serve_scores():
    """Cache-fed serving == the reference dense-table forward."""
    import jax
    import jax.numpy as jnp

    from repro.configs import base as cfgs
    from repro.nn import recsys as recsys_mod
    from repro.serve.engine import RecsysServeEngine

    cfg = cfgs.reduced(cfgs.get_arch("mind"))
    params = recsys_mod.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    nreq = 5
    payloads = [{
        "hist": rng.integers(0, cfg.n_items, cfg.hist_len).astype(np.int32),
        "hist_mask": rng.random(cfg.hist_len) < 0.9,
        "candidates": rng.integers(0, cfg.n_items, 16).astype(np.int32),
    } for _ in range(nreq)]

    eng = RecsysServeEngine(
        params, cfg,
        CacheConfig(budget_bytes=64 * cfg.embed_dim * 4, tile_e=128),
        SchedulerConfig(max_batch=4, max_queue=16),
        clock=VirtualClock(), service_model=lambda n: 1e-3,
    )
    reqs = [eng.submit(p) for p in payloads]
    eng.run_until_idle()
    assert all(r.status == "done" for r in reqs)

    batch = {k: jnp.asarray(np.stack([p[k] for p in payloads]))
             for k in payloads[0]}
    ref = np.asarray(recsys_mod.serve_scores(params, cfg, batch))
    got = np.stack([r.result for r in reqs])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    assert eng.metrics.counters["completed"] == nreq
    assert eng.metrics.counters["batches"] == 2  # 4 + 1 (partial, padded)


def test_gnn_engine_blocks_match_dense_gather():
    """GIN forward over cache-gathered features == dense-gathered features."""
    import jax
    import jax.numpy as jnp

    from repro.configs import base as cfgs
    from repro.graph import generate, sampler
    from repro.nn import gnn as gnn_mod
    from repro.serve.engine import GNNServeEngine

    g = generate.rmat(8, 4, seed=0)                  # 256 nodes, power-law
    cfg = cfgs.reduced(cfgs.get_arch("gin-tu"))
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((g.num_nodes, 8)).astype(np.float32)
    params = gnn_mod.init(jax.random.PRNGKey(0), cfg, 8)
    eng = GNNServeEngine(
        params, cfg, g, feats,
        CacheConfig(budget_bytes=64 * 8 * 4, tile_e=128),
        SchedulerConfig(max_batch=2, max_queue=8),
        fanout=(3, 3), seeds_per_req=2, clock=VirtualClock(),
        service_model=lambda n: 1e-3,
    )
    blocks = sampler.sample_blocks(g, np.array([1, 5, 9, 200]), (3, 3),
                                   np.random.default_rng(7))
    got = eng.forward_blocks(blocks)
    x = jnp.where(jnp.asarray(blocks.node_mask)[:, None],
                  jnp.asarray(feats[blocks.node_ids]), 0.0)
    ref = gnn_mod.apply(params, cfg, {
        "x": x, "src": jnp.asarray(blocks.src), "dst": jnp.asarray(blocks.dst),
        "emask": jnp.asarray(blocks.emask),
    })
    np.testing.assert_allclose(got, np.asarray(ref)[blocks.seeds_local],
                               rtol=1e-5, atol=1e-6)
    # queued path: per-request logits with the right shape
    r1 = eng.submit({"seeds": np.array([0, 1])})
    r2 = eng.submit({"seeds": np.array([2, 3])})
    eng.run_until_idle()
    assert eng.metrics.counters["completed"] == 2
    assert r1.result.shape == r2.result.shape == (2, cfg.d_out)
    assert np.isfinite(r1.result).all()


def test_lm_loop_partial_batch_counts_served_tokens():
    """requests % batch != 0: the loop must serve exactly requests*decode
    tokens (the old driver padded the last batch and misreported)."""
    from repro.serve.engine import lm_loop

    stats = lm_loop(arch="minitron-8b", smoke=True, requests=5, batch=4,
                    prefill=8, decode=4)
    assert stats["requests"] == 5
    assert stats["tokens"] == 5 * 4


def test_launch_serve_cli_recsys(tmp_path):
    from repro.launch import serve as serve_cli

    out = tmp_path / "s.json"
    snap = serve_cli.main([
        "--engine", "recsys", "--smoke", "--requests", "24", "--batch", "4",
        "--qps", "1e9", "--budget-kb", "4", "--deadline-ms", "1e9",
        "--json", str(out),
    ])
    assert snap["counters"]["completed"] == 24
    assert 0.0 < snap["hit_rate"] <= 1.0
    assert out.exists()
