"""Compile the main paths' programs for a described TPU v5e chip.

Nothing runs: each test lowers a kernel or a step with its shapes placed on
one chip of a described ``v5e:2x2`` topology, and the TPU compiler accepts
or refuses it. Only one process at a time may load the TPU library, so the
topology is described inside a fixture, never while a module is imported,
and these tests stay in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_hot_gather_compiles_at_the_vmem_cap(one_chip):
    """MIND rows (64 lanes padded to 128) at the largest pinned block the
    plan allows compile to a Mosaic kernel; one sublane tile more does
    not fit the kernel's scoped VMEM."""
    from repro.core import plan as plan_mod
    from repro.kernels.hot_gather.hot_gather import IDX_TILE, hot_gather_hot_part

    cap = plan_mod.kernel_hot_rows(128 * 4, IDX_TILE)
    assert cap > 0

    def lower(rows):
        return hot_gather_hot_part.lower(
            _sds((rows, 128), jnp.float32, one_chip),
            _sds((4 * IDX_TILE,), jnp.int32, one_chip),
            tile_e=IDX_TILE, interpret=False)

    assert "tpu_custom_call" in lower(cap).compile().as_text()
    with pytest.raises(Exception, match="vmem"):
        lower(cap + plan_mod.SUBLANES).compile()


def _graph(n, m, weights, sharding):
    from repro.graph.csr import DeviceCSR

    return DeviceCSR(indptr=_sds((n + 1,), jnp.int32, sharding),
                     indices=_sds((m,), jnp.int32, sharding),
                     dst=_sds((m,), jnp.int32, sharding),
                     weights=_sds((m,), jnp.float32, sharding)
                     if weights else None, num_nodes=n)


def test_pagerank_compiles_at_scale_22(one_chip):
    from repro.apps.pagerank import pagerank_loop

    n = 1 << 22
    m = 16 * n  # Graph500 edge factor 16, before duplicates are dropped
    g = _graph(n, m, False, one_chip)
    compiled = pagerank_loop.lower(g, damping=0.85, tol=1e-6 / n,
                                   max_iters=300).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes >= 2 * m * 4


def test_edge_map_scopes_survive_the_chip_fusion_pass(one_chip):
    """At the benchmark's ``kron21`` shapes (2^21 vertices, 2^26 arc slots)
    every fusion over the arcs carries one of its app's scopes, and the
    gather, the active-flag gather and the reduction each keep fusions of
    their own, so a device trace reads them apart. The count of such
    fusions per scope is pinned: the per-scope benchmark metrics read
    these fusions, so a change that moves work between scopes changes
    what those metrics measure and has to show here.

    PageRank's pull reduction is a segmented scan over the in-CSR's sorted
    rows (``engine.reduce_rows``), with no scatter under
    ``edge_map.reduce``; SSSP's push reduction keeps its scatter-min."""
    import collections
    import re

    from test_obs import arc_sized_fusions

    from repro import obs
    from repro.apps.pagerank import pagerank_loop
    from repro.apps.sssp import sssp_loop

    n, m = 1 << 21, 1 << 26
    programs = {
        "pagerank": (pagerank_loop.lower(
            _graph(n, m, False, one_chip), 0.85,
            _sds((), jnp.float32, one_chip), max_iters=20),
            {obs.GATHER: 4, obs.REDUCE: 13, obs.OUT_DEGREE: 2},
            {obs.OUT_DEGREE: 1}),
        "sssp": (sssp_loop.lower(
            _graph(n, m, True, one_chip), _sds((), jnp.int32, one_chip)),
            {obs.GATHER: 1, obs.FRONTIER: 4, obs.REDUCE: 2},
            {obs.REDUCE: 1}),
    }
    for app, (lowered, per_scope, scatters) in programs.items():
        text = lowered.compile().as_text()
        scope_of = obs.scopes_of_hlo(text)
        fusions = arc_sized_fusions(text, m)
        assert collections.Counter(scope_of[f] for f in fusions) == \
            per_scope, app
        found = re.findall(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = \S+ scatter\(",
                           text, re.MULTILINE)
        assert collections.Counter(scope_of[f] for f in found) == \
            scatters, app


def test_mind_serve_step_compiles_at_published_widths(one_chip):
    """The routed scoring function of ``RecsysServeEngine`` for MIND
    (hist 50, 4 interests, 3 capsule iterations, d 64, d_hidden 256)."""
    import dataclasses

    import numpy as np

    from repro.configs import base as cfgs
    from repro.nn import recsys
    from repro.serve.cache import CacheConfig
    from repro.serve.engine import RecsysServeEngine
    from repro.serve.scheduler import SchedulerConfig

    cfg = cfgs.get_arch("mind")
    # the routed function never sees the table: a short one builds the
    # engine here without the published 2^21 rows
    params = recsys.init(jax.random.PRNGKey(0),
                         dataclasses.replace(cfg, n_items=256))
    batch = 8
    engine = RecsysServeEngine(params, cfg, CacheConfig(budget_bytes=1 << 14),
                               SchedulerConfig(max_batch=batch))
    a_params = jax.tree_util.tree_map(
        lambda a: _sds(np.shape(a), a.dtype, one_chip), engine.params)
    h, d, c = cfg.hist_len, cfg.embed_dim, 32
    compiled = engine._routed.lower(
        a_params,
        _sds((batch, h, d), jnp.float32, one_chip),
        _sds((batch, h), jnp.int32, one_chip),
        _sds((batch, h), jnp.bool_, one_chip),
        _sds((batch, c, d), jnp.float32, one_chip)).compile()
    out = compiled.out_info
    assert out.shape == (batch, c)
