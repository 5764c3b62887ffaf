"""Record the small trace that ``test_trace.py`` reads, on a TPU.

    python chipbench/tests/record_trace.py OUT_DIR

PageRank jobs on a scale-12 Kronecker graph through the harness's own
window and spans, traced for about 50 ms; the ``.xplane.pb`` is copied to
``OUT_DIR/small.xplane.pb`` and the reduction's numbers are printed.
"""
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import run, trace  # noqa: E402


def main(out_dir: str) -> int:
    import jax

    why = run.device_check(jax.devices(), 1)
    if why:
        print(why, file=sys.stderr)
        return 2
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    _, cfg, traffic = run.find_cell(bench, "kron21.pr")
    job = run.load_module(ROOT / "chipbench" / "jobs" / "pagerank.py")
    state = job.setup({**cfg, "scale": 12}, traffic, 0)
    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        outputs, window_s = run.window(job, state, 0.05, tmp)
        path = trace.find_xplane(tmp)
        Path(out_dir).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, Path(out_dir) / "small.xplane.pb")
        profile = trace.load(path)
        for plane in profile.planes:
            print("plane", plane.name)
            for line in plane.lines:
                evs = list(line.events)
                print("  line", repr(line.name), len(evs),
                      [e.name for e in evs[:6]])
        s = trace.summarize(profile)
        print(json.dumps({"jobs": len(outputs), "host_window_s": window_s,
                          "window_s": s.window_s, "busy_s": s.busy_s,
                          "exposed_collective_s": s.exposed_collective_s,
                          "breakdown": trace.breakdown(s)}))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
