"""Whole runs of the harness on the CPU at a small scale: sound runs come
out correct, and runs whose timed path is broken underneath, or replaced by
the bfloat16 control, come out not correct."""
import json
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, run

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"scale": 11}


def run_small(capsys, workload, seed=3, seconds=0.2, trace=0):
    args = types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)
    assert run.run_cell(args, require_chip=False, cfg_override=SMALL) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("workload", ["kron21.pr", "kron21.sssp"])
def test_sound_run_is_correct(capsys, workload):
    res = run_small(capsys, workload, seed=2**31 + 99)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    job_s = "pr.job_s" if workload == "kron21.pr" else "sssp.job_s"
    assert set(res["metrics"]) == {job_s, "setup_s"}
    assert list(res)[-1] == "checks"


def test_traced_run_without_device_time_reads_no_device_metric(capsys):
    # the CPU's trace has no TPU plane: the device readers find nothing
    res = run_small(capsys, "kron21.sssp", seed=41, trace=1)
    assert res["correct"] is True
    assert set(res["metrics"]) <= {"sssp.idle_share"}


def _pad_half(g):
    """The graph with the second half of its edge slots made padding."""
    m = g.indices.shape[0]
    keep = jnp.arange(m) < m // 2
    n = g.num_nodes
    return type(g)(indptr=g.indptr, indices=jnp.where(keep, g.indices, n),
                   dst=jnp.where(keep, g.dst, n), weights=g.weights,
                   num_nodes=n)


def _faults(app, initial):
    return {
        "answer_altered": lambda g, *a, **k: app(g, *a, **k).at[1].add(1.0),
        "state_unchanged": lambda g, *a, **k: initial(g, *a, **k),
        "half_the_edges": lambda g, *a, **k: app(_pad_half(g), *a, **k),
    }


def _pr_initial(g, **_):
    return jnp.full((g.num_nodes,), 1.0 / g.num_nodes, jnp.float32)


def _sssp_initial(g, source, **_):
    return jnp.full((g.num_nodes,), jnp.inf, jnp.float32).at[source].set(0.0)


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged",
                                   "half_the_edges"])
@pytest.mark.parametrize("workload,module,name,initial", [
    ("kron21.pr", "repro.apps.pagerank", "pagerank", _pr_initial),
    ("kron21.sssp", "repro.apps.sssp", "sssp", _sssp_initial),
])
def test_broken_timed_path_is_not_correct(capsys, monkeypatch, fault,
                                          workload, module, name, initial):
    mod = sys.modules.get(module) or __import__(module, fromlist=[name])
    app = getattr(mod, name)
    monkeypatch.setattr(mod, name, _faults(app, initial)[fault])
    res = run_small(capsys, workload)
    assert res["correct"] is False and res["failed"] >= 1


@pytest.mark.parametrize("workload", ["kron21.pr", "kron21.sssp"])
def test_bfloat16_control_fails_its_limit(workload):
    for r in control.readings(workload, [1, 2, 3], SMALL):
        for name, limit in r["limits"].items():
            assert r["program"][name] <= limit, r
            assert r["control_bf16"][name] > limit, r


def test_pagerank_one_iteration_short_fails_its_limit():
    for r in control.readings("kron21.pr", [4, 5, 6], SMALL):
        limit = r["limits"]["rank_l1_gap"]
        assert r["one_iteration_short"]["rank_l1_gap"] > limit, r


def test_no_tpu_no_result():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "kron21.pr", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu",
                                             "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "kron21.pr",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu",
                                             "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""


def test_metrics_for_cells():
    """Every cell reports ``setup_s`` and at least one more end-to-end
    metric (those that name it, and those that name no cell), and with
    ``--trace 1`` the per-layer metrics that name it, each moving one of
    its end-to-end metrics; every per-layer metric names its cells and
    every metric has a reader. The expected sets come from
    ``BENCHMARK.json``, so a new cell or metric is covered as it lands."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = lambda ms: {m["name"] for m in ms}  # noqa: E731
    assert all("workloads" in m for m in bench["per_layer"])
    for cell in bench["workloads"]:
        name = cell["name"]
        e2e = {m["name"] for m in bench["end_to_end"]
               if name in m.get("workloads", [name])}
        assert "setup_s" in e2e and len(e2e) >= 2, name
        assert names(run.metrics_for(bench, cell, False)) == e2e
        layer = [m for m in bench["per_layer"] if name in m["workloads"]]
        assert layer and all(m["moves"] in e2e for m in layer), name
        assert names(run.metrics_for(bench, cell, True)) == names(layer)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
