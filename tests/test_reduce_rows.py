"""The pull edge map's reduction over sorted CSR rows
(``engine.reduce_rows``) against the scatters it replaces, and PageRank on
a padded in-CSR as the benchmark's generator lays it out."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import engine
from repro.apps.pagerank import pagerank_loop
from repro.graph.csr import CSR

# the benchmark's package lies beside ``src/``
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import gen, reference  # noqa: E402

L = engine.LANES

# reducer -> the scatter it must agree with
SCATTERS = {
    "sum": (engine.sum_reduce, jax.ops.segment_sum),
    "min": (engine.min_reduce, jax.ops.segment_min),
    "max": (engine.max_reduce, jax.ops.segment_max),
    "or": (engine.or_reduce, engine.or_reduce),
}


def _rows(degrees, padding=0):
    """(rows, indptr, n) of a CSR with these row lengths, then ``padding``
    slots in row ``n`` past ``indptr[n]``, as ``chipbench/gen.py`` puts
    the arcs that dedup removed."""
    degrees = np.asarray(degrees, np.int64)
    n = degrees.size
    indptr = np.zeros(n + 1, np.int32)
    np.cumsum(degrees, out=indptr[1:])
    rows = np.concatenate([np.repeat(np.arange(n), degrees),
                           np.full(padding, n)]).astype(np.int32)
    return rows, indptr, n


def _case(name, rng):
    if name == "zero_degree":       # two rows in five are empty
        d = rng.integers(1, 9, 300) * (rng.random(300) < 0.6)
        return _rows(d)
    if name == "hub":               # one row over many 128-slot lane rows
        d = rng.integers(0, 6, 40)
        d[17] = 9 * L + 5
        return _rows(d)
    if name == "lane_boundaries":   # rows that start and end on lane edges
        return _rows([L, 2 * L, 0, L // 2, L // 2, L, 0, 3 * L])
    if name == "ragged_slots":      # a slot count not a multiple of 128
        d = rng.integers(0, 7, 293)
        d[-1] += (37 - d.sum()) % L
        return _rows(d)
    if name == "one_slot":
        return _rows([0, 1, 0])
    if name == "all_padding":       # no real arc: every row is empty
        return _rows(np.zeros(20, np.int64), padding=300)
    if name == "gen_padding":       # real rows, then padding in row n
        return _rows(rng.integers(0, 9, 200), padding=211)
    raise ValueError(name)


CASES = ["zero_degree", "hub", "lane_boundaries", "ragged_slots", "one_slot",
         "all_padding", "gen_padding"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("reducer", sorted(SCATTERS))
def test_reduce_rows_matches_the_scatter(reducer, case):
    """Every vertex gets its row's reduction (the identity where the row is
    empty); padding slots, whose messages the property gather fills with
    NaN, reach no vertex."""
    red, scatter = SCATTERS[reducer]
    rng = np.random.default_rng(sorted(CASES).index(case))
    rows, indptr, n = _case(case, rng)
    m = rows.size
    if reducer == "or":
        prop = rng.integers(0, 2, n).astype(np.float32)
    else:
        prop = rng.standard_normal(n).astype(np.float32)
    # the property gather as the pull edge map does it: padding slots point
    # past the last vertex and read NaN
    src = np.where(rows < n, rng.integers(0, n, m), n).astype(np.int32)
    msgs = jnp.take(jnp.asarray(prop), jnp.asarray(src))
    assert np.isnan(np.asarray(msgs)[rows == n]).all()

    got = np.asarray(engine.reduce_rows(msgs, jnp.asarray(rows),
                                        jnp.asarray(indptr), red))
    want = np.asarray(scatter(msgs, jnp.asarray(rows), n))
    assert got.dtype == want.dtype and got.shape == (n,)
    assert not np.isnan(got).any()
    if reducer == "sum":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("reducer", sorted(SCATTERS))
def test_edge_map_pull_reduces_every_reducer_over_rows(reducer):
    """``edge_map_pull`` gives what the scatter over ``g.dst`` gave, for
    each reducer, with messages of more than one lane each."""
    red, scatter = SCATTERS[reducer]
    rng = np.random.default_rng(5)
    rows, indptr, n = _rows(rng.integers(0, 12, 150))
    g = CSR(indptr=indptr.astype(np.int64),
            indices=rng.integers(0, n, rows.size).astype(np.int32),
            num_nodes=n).device()
    prop = jnp.asarray(rng.integers(0, 5, (n, 3)).astype(np.float32))
    got = engine.edge_map_pull(g, prop, reduce_fn=red)
    want = scatter(jnp.take(prop, g.indices, axis=0), g.dst, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("seed", [3, 4100000021, 2**31 + 7])
def test_pagerank_on_a_padded_in_csr_matches_float64(seed):
    """PageRank on the benchmark generator's in-CSR (Kronecker scale 9,
    padding slots past the last vertex) stops at the float64 power
    iteration's iterate and lies within the benchmark's ``rank_l1_gap``
    limit of it."""
    root = Path(__file__).resolve().parents[1] / "chipbench"
    cfg = json.loads((root / "configs" / "kron21.json").read_text())
    traffic = json.loads((root / "traffic" / "pr.json").read_text())
    cfg["scale"] = 9
    g, m, _ = gen.build(cfg, seed, "in")
    n = g.num_nodes
    assert m < g.indices.shape[0]  # some slots are padding
    rank, stats = pagerank_loop(g, traffic["damping"], traffic["l1"] / n,
                                max_iters=traffic["max_iters"])
    iters, ref, _ = reference.pagerank_iterates(
        np.asarray(g.indptr), np.asarray(g.indices[:m]), n,
        traffic["damping"], traffic["l1"], traffic["max_iters"])
    assert int(stats["iterations"]) == iters
    gap = np.abs(np.asarray(rank, np.float64) - ref[iters]).sum()
    assert gap <= traffic["limits"]["rank_l1_gap"]
