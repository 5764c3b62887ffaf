"""The device generator against the host generator and the host DBG order,
at small scales on the CPU."""
import numpy as np
import pytest

from chipbench import gen
from repro.core.hotset import skew_stats
from repro.core.reorder import dbg_order
from repro.graph import generate
from repro.graph.csr import symmetrize

CFG = {"scale": 12, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
       "weights": [1, 255], "symmetric": True}


def drawn_edges(seed, cfg=CFG):
    src, dst, w, out_deg, m = gen._edges(
        gen.seed_key(seed), scale=cfg["scale"], edge_factor=cfg["edge_factor"],
        a=cfg["a"], b=cfg["b"], c=cfg["c"], w_lo=cfg["weights"][0],
        w_hi=cfg["weights"][1])
    return (np.asarray(src), np.asarray(dst), np.asarray(w),
            np.asarray(out_deg), int(m))


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11])
def test_dbg_permutation_equals_host_dbg_order(seed):
    n = 1 << CFG["scale"]
    src, _, _, out_deg, m = drawn_edges(seed)
    real = src < n
    degree = np.bincount(src[real], minlength=n)
    assert (degree == out_deg).all() and degree.sum() == m
    _, m2, rank = gen.build(CFG, seed, "in")
    assert m2 == m
    np.testing.assert_array_equal(np.asarray(rank), dbg_order(degree))


def test_skew_agrees_with_host_rmat_across_seeds():
    dev, host = [], []
    for seed in range(4):
        g, _, _ = gen.build(CFG, seed, "out")
        deg = np.diff(np.asarray(g.indptr))
        dev.append(skew_stats(deg))
        host.append(skew_stats(symmetrize(generate.rmat(
            CFG["scale"], 16, seed=seed)).out_degree))
    for field, tol in (("hot_fraction", 0.01), ("edge_coverage", 0.01),
                       ("avg_degree", 0.2)):
        d = np.mean([getattr(s, field) for s in dev])
        h = np.mean([getattr(s, field) for s in host])
        assert abs(d - h) <= tol, (field, d, h)


@pytest.mark.parametrize("direction", ["in", "out"])
def test_csr_is_deduplicated_padded_and_weighted(direction):
    n = 1 << CFG["scale"]
    g, m, _ = gen.build(CFG, 3, direction)
    indptr, col, row, w = (np.asarray(a) for a in
                           (g.indptr, g.indices, g.dst, g.weights))
    assert col.size == 2 * n * CFG["edge_factor"] and indptr[-1] == m
    np.testing.assert_array_equal(np.diff(indptr), np.bincount(row[:m],
                                                               minlength=n))
    assert (col[m:] == n).all() and (row[m:] == n).all()
    assert (col[:m] < n).all() and (row[:m] < n).all()
    assert (col[:m] != row[:m]).all()                  # no self-loops
    key = row[:m].astype(np.int64) * n + col[:m]
    assert (np.diff(key) > 0).all()                    # sorted, no duplicates
    assert (w == np.round(w)).all() and w.min() >= 1 and w.max() <= 255
    assert w[:m].min() == 1 and w[:m].max() == 255


def test_symmetric_graph_has_both_arcs_with_one_weight():
    n = 1 << CFG["scale"]
    g, m, _ = gen.build(CFG, 4, "out")
    col, row, w = (np.asarray(a)[:m] for a in (g.indices, g.dst, g.weights))
    fwd = dict(zip(row.astype(np.int64) * n + col, w))
    back = dict(zip(col.astype(np.int64) * n + row, w))
    assert fwd == back
    with pytest.raises(ValueError):
        gen.build({**CFG, "symmetric": False}, 4, "out")


def test_same_seed_same_graph_and_large_seeds_differ():
    a, _, _ = gen.build(CFG, 2**32 + 7, "in")
    b, _, _ = gen.build(CFG, 2**32 + 7, "in")
    c, _, _ = gen.build(CFG, 7, "in")
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
    assert not np.array_equal(np.asarray(a.indices), np.asarray(c.indices))
