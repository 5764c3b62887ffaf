"""Readings that set a cell's limits: the program, its precision control
and its planted faults, compared with the float64 reference, seed by seed.

    python chipbench/control.py --workload kron21.pr --seeds 1 2 3

For each seed it builds the cell's graph on the device, runs one job of the
program, the same job of the control and of each fault the job module
plants (``faults``, where it has one), and prints the compared numbers as
one JSON line. The control is the job module's ``control``: the plain
reference put in the program's place and computed in bfloat16, the
precision below the configuration's float32, so a change that lowered the
apps' precision would read like it. The benchmark's own runs never run
this; the limits in the traffic files come from its readings
(``PERF.md``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings(workload: str, seeds, cfg_override=None):
    """Yields one dict per seed: the compared numbers of the program, the
    control and each fault."""
    from chipbench import run

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, cfg, traffic = run.find_cell(bench, workload)
    if cfg_override:
        cfg = {**cfg, **cfg_override}
    job = run.load_module(BENCH / "jobs" / f"{traffic['job']}.py")
    for seed in seeds:
        t0 = time.perf_counter()
        state = job.setup(cfg, traffic, seed)
        outs = {"program": job.run(state, 0),
                "control_bf16": job.control(state, traffic, jnp.bfloat16)}
        host = job.host_graph(state)
        if hasattr(job, "faults"):
            outs.update(job.faults(state, traffic, host))
        outs = {k: jax.device_get(v) for k, v in outs.items()}
        del state
        checks = {k: job.check(host, [v], traffic, seed)[0]
                  for k, v in outs.items()}
        yield {"workload": workload, "seed": seed,
               **{k: {n: v for n, v, _ in c} for k, c in checks.items()},
               "limits": {n: lim for n, _, lim in checks["program"]},
               "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import run

    why = run.device_check(jax.devices(), 1)
    if why:
        print(f"[control] {why}", file=sys.stderr)
        return 2
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for r in readings(args.workload, args.seeds):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
