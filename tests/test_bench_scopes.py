"""The benchmark's device time by program scope (``chipbench/scopes.py``)
and the per-layer readers built on it, on synthetic traces over the
programs' real CPU compiles. The scoped traces recorded on a TPU v5e are
read in ``chipbench/tests/test_scopes.py``."""
import sys
from pathlib import Path

import numpy as np
import pytest

import repro
from repro import obs
from repro.apps.pagerank import pagerank
from repro.apps.sssp import sssp
from repro.graph import generate
from repro.graph.csr import CSR, transpose

# the benchmark's package lies beside ``src/``
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import run, scopes  # noqa: E402

READERS = {  # metric -> (job, scope or None for the counters)
    "pr.gather_ms": ("pagerank", obs.GATHER),
    "pr.reduce_ms": ("pagerank", obs.REDUCE),
    "pr.degree_ms": ("pagerank", obs.OUT_DEGREE),
    "sssp.gather_ms": ("sssp", obs.GATHER),
    "sssp.frontier_ms": ("sssp", obs.FRONTIER),
    "sssp.reduce_ms": ("sssp", obs.REDUCE),
    "sssp.frontier_share": ("sssp", None),
}


def _reader(metric):
    return run.load_module(run.BENCH / "metrics" / f"{metric}.py")


class Trace:
    def __init__(self, op_self_s, busy_s):
        self.op_self_s, self.busy_s = op_self_s, busy_s


def test_self_seconds_by_scope():
    ops = {"%fusion.16 = f32[4] fusion(s32[64] %p.1), kind=kCustom": 3.0,
           "%fusion.15 = f32[64] fusion(f32[4] %p.0), kind=kCustom": 2.0,
           "%fusion.2 = f32[4] fusion(s32[64] %p.1)": 0.5,
           "%copy-done.2 = f32[4] copy-done(%copy-start.2)": 0.25,
           "%while.7 = (f32[4]) while(%tuple.3)": 0.125}
    scope_of = {"fusion.16": obs.REDUCE, "fusion.15": obs.GATHER,
                "fusion.2": obs.REDUCE, "copy-done.2": None}
    assert scopes.instruction(next(iter(ops))) == "fusion.16"
    assert scopes.self_seconds(ops, scope_of) == {
        obs.REDUCE: 3.5, obs.GATHER: 2.0, scopes.UNSCOPED: 0.375}
    assert scopes.self_seconds({}, scope_of) == {}


@pytest.fixture(scope="module")
def windows():
    """Both apps run as a cell runs them, on small graphs on the CPU: once
    for set-up, then two jobs. Per job: a synthetic trace that gives each
    scope of the app's compiled program a known time, and the program's
    counts of the two jobs."""
    obs.clear()
    g = generate.rmat(8, 8, seed=5)
    for tol in (float("inf"), 1e-6, 1e-7):
        pagerank(g.device(), 0.85, tol, max_iters=50)
    w = np.random.default_rng(5).integers(1, 256, g.num_edges)
    g_out = transpose(CSR(g.indptr, g.indices, g.num_nodes,
                          w.astype(np.float32))).device()
    for source in (0, 1, 2):
        sssp(g_out, source, max_iters=64)
    out = {}
    for job in ("pagerank", "sssp"):
        calls = obs.calls(job)[-2:]
        seconds, op_self_s = {}, {}
        for name, scope in sorted(obs.scope_map(calls[0]).items()):
            if scope and scope not in seconds:
                seconds[scope] = 0.5 + len(seconds)
                op_self_s[f"%{name} = f32[] fusion()"] = seconds[scope]
        out[job] = (op_self_s, seconds, [obs.counts(c) for c in calls])
    yield out
    obs.clear()


def _ctx(job, op_self_s, busy_s=(9.0,), jobs=2):
    return {"job": job, "jobs": jobs, "trace": Trace(op_self_s, list(busy_s)),
            "work": {}, "peaks": {}}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_the_window(windows, metric):
    job, scope = READERS[metric]
    op_self_s, seconds, counts = windows[job]
    value = _reader(metric).read(_ctx(job, op_self_s))
    if scope is None:
        useful = sum(int(c["frontier_arcs"].sum()) for c in counts)
        attempted = sum(int(c["arcs_relaxed"].sum()) for c in counts)
        assert 0 < useful < attempted
        assert value == pytest.approx(100.0 * useful / attempted)
        return
    per = {"pagerank": "iterations", "sssp": "rounds"}[job]
    # per job for the out-degree, counted once a job; else per iteration or
    # round the program counted, more than the jobs
    units = 2 if scope == obs.OUT_DEGREE else sum(int(c[per]) for c in counts)
    assert units > 2 or scope == obs.OUT_DEGREE
    assert value == pytest.approx(1000.0 * seconds[scope] / units)


@pytest.mark.parametrize("case", ["another_job", "no_trace", "no_device_time",
                                  "fewer_calls_than_jobs", "no_scope_time",
                                  "program_without_obs"])
@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_nothing(windows, monkeypatch, metric, case):
    job, scope = READERS[metric]
    op_self_s = windows[job][0]
    if case == "program_without_obs":
        # a program older than ``repro.obs``, as the parent of a change is
        monkeypatch.delattr(repro, "obs")
        monkeypatch.setitem(sys.modules, "repro.obs", None)
    ctx = {
        "another_job": _ctx({"pagerank": "sssp", "sssp": "pagerank"}[job],
                            op_self_s),
        "no_trace": {**_ctx(job, op_self_s), "trace": None},
        "no_device_time": _ctx(job, op_self_s, busy_s=()),
        "fewer_calls_than_jobs": _ctx(job, op_self_s, jobs=obs.KEEP + 1),
        "no_scope_time": _ctx(job, {}),
        "program_without_obs": _ctx(job, op_self_s),
    }[case]
    value = _reader(metric).read(ctx)
    if case == "no_scope_time" and scope is None:
        assert value is not None   # the counters need no trace time
    else:
        assert value is None
