"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload kron21.pr --seed 7 --seconds 51 --trace 0

The cell is looked up by name in ``BENCHMARK.json``; its configuration file,
its traffic file (``chipbench/traffic/<traffic>.json``), the job module
that traffic names (``chipbench/jobs/<job>.py``) and each metric's reader
(``chipbench/metrics/<metric>.py``) are found by name, so a new cell, mix or
metric is a new file.

One run is one process, in four steps:

1. set-up: build the graph on the device from ``--seed`` and compile and
   load the job's program (``setup_s``, from the start of this process);
2. the window: jobs back to back until ``--seconds`` have passed, the last
   one allowed to finish; with ``--trace 1`` the window is traced;
3. the check: every job's answer against the float64 reference on the
   host, after the peak device memory is read and the device arrays are
   freed;
4. the result: one JSON line on stdout, with the compared numbers and their
   limits also as the last lines on stderr.

It exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    """(cell, configuration, traffic) dicts for ``workload``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def metrics_for(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics, or with
    ``trace`` the per-layer metrics that name it or that move one of its
    end-to-end metrics."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


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


def device_check(devices, chips: int) -> str | None:
    """Why ``devices`` cannot run a cell of ``chips`` chips, or None."""
    if devices[0].platform != "tpu":
        return f"platform {devices[0].platform!r} is not a TPU"
    if len(devices) < chips:
        return f"{chips} chips asked, {len(devices)} found"
    return None


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"chipbench/peaks.json")
    return table[kind]


def window(job, state, seconds: float, trace_dir: str | None):
    """Jobs back to back until ``seconds`` have passed; the last one
    finishes. Returns (outputs, window seconds)."""
    import jax

    outputs = []
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench:job"):
                    outputs.append(job.run(state, len(outputs)))
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return outputs, elapsed


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def run_cell(args, *, require_chip: bool = True,
             cfg_override: dict | None = None) -> int:
    """Everything after argument parsing. ``require_chip=False`` and
    ``cfg_override`` exist for the tests, which drive a run on the CPU at a
    small size; the command line never sets them."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, cfg, traffic = find_cell(bench, args.workload)
    if cfg_override:
        cfg = {**cfg, **cfg_override}
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program under {ROOT / 'src'}: nothing to measure")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax

    from chipbench import trace

    devices = jax.devices()
    why = device_check(devices, cell["chips"])
    if why and require_chip:
        log(f"[device] {why}: no result")
        return 2
    cache_dir = None
    if not why:
        from repro.launch import compile_cache

        # every program, however quick to compile, is kept for the next run
        cache_dir = compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    used = devices[:cell["chips"]]
    peaks = peaks_for(used[0].device_kind) if require_chip else {}
    job = load_module(BENCH / "jobs" / f"{traffic['job']}.py")
    log(f"[setup] {cell['name']}: config {cell['config']} traffic "
        f"{cell['traffic']} job {traffic['job']} seed {args.seed} on "
        f"{len(used)} x {used[0].device_kind}; compile cache {cache_dir}")

    with CompileCounter() as counter:
        with jax.profiler.TraceAnnotation("bench:setup"):
            state = job.setup(cfg, traffic, args.seed)
        setup_s = time.perf_counter() - T_START
        setup_compiles = counter.compiles
        log(f"[setup] {setup_s:.3f} s, {setup_compiles} compiles, "
            f"{counter.cache_hits} read from the compile cache")
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") \
            if args.trace else None
        try:
            outputs, window_s = window(job, state, args.seconds, trace_dir)
            window_compiles = counter.compiles - setup_compiles
            log(f"[window] {len(outputs)} jobs in {window_s:.3f} s, "
                f"{window_compiles} compiles inside the window")
            summary = None
            if trace_dir is not None:
                summary = trace.summarize(
                    trace.load(trace.find_xplane(trace_dir)))
        finally:
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)

    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    host = job.host_graph(state)
    outputs = [jax.device_get(o) for o in outputs]
    del state
    t0 = time.perf_counter()
    checks, failed, work = job.check(host, outputs, traffic, args.seed)
    log(f"[check] reference and comparison {time.perf_counter() - t0:.3f} s")

    ctx = {"setup_s": setup_s, "window_s": window_s, "jobs": len(outputs),
           "job": traffic["job"], "work": work, "peaks": peaks,
           "trace": summary}
    metrics = {}
    for m in metrics_for(bench, cell, bool(args.trace)):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
    correct = all(v <= limit for _, v, limit in checks)
    result = {"correct": correct, "attempted": len(outputs),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = trace.breakdown(summary)
    result["checks"] = {name: {"value": v, "limit": limit}
                        for name, v, limit in checks}
    print(json.dumps(result), flush=True)
    for name, v, limit in checks:
        log(f"check {name} = {v!r} limit {limit!r} "
            f"{'ok' if v <= limit else 'FAIL'}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return run_cell(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
