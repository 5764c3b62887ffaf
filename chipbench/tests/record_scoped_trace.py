"""Record the scoped traces that ``test_scopes.py`` reads, on a TPU.

    python chipbench/tests/record_scoped_trace.py OUT_DIR

For PageRank and then SSSP: jobs on a scale-12 Kronecker graph through the
harness's own window and spans, traced for about 50 ms. Each app's
``.xplane.pb`` is copied to ``OUT_DIR/scoped_<app>.xplane.pb`` and the
compiled HLO text of the program its window ran to
``OUT_DIR/scoped_<app>.hlo.txt``, the checkout's path in both replaced by
``<checkout>`` and the HLO's table of source locations left out; the
reduction by scope is printed.
"""
import json
import re
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import run, scopes, trace  # noqa: E402

CELLS = (("kron21.pr", "pagerank"), ("kron21.sssp", "sssp"))


def _anonymised(data: bytes) -> bytes:
    """``data`` with the checkout's path replaced by ``<checkout>``, cut or
    padded to the same length so that the protobuf stays valid."""
    root = str(ROOT).encode()
    return data.replace(root, b"<checkout>".ljust(len(root), b"_")[:len(root)])


def _without_locations(text: str) -> str:
    """The HLO module line and its computations, without the tables of
    file names, functions and stack frames between them."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if re.match(r"^(ENTRY )?%", line))
    return "\n".join([lines[0], ""] + lines[first:]) + "\n"


def _brief(counts: dict) -> dict:
    """A call's counts, the per-round ones cut to its rounds."""
    n = int(counts.get("rounds", 0))
    return {k: v.tolist() if v.ndim == 0 else v[:n].tolist()
            for k, v in counts.items()}


def main(out_dir: str) -> int:
    import jax

    from repro import obs

    why = run.device_check(jax.devices(), 1)
    if why:
        print(why, file=sys.stderr)
        return 2
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell, app in CELLS:
        _, cfg, traffic = run.find_cell(bench, cell)
        job = run.load_module(ROOT / "chipbench" / "jobs" /
                              f"{traffic['job']}.py")
        state = job.setup({**cfg, "scale": 12}, traffic, 0)
        tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
        try:
            outputs, window_s = run.window(job, state, 0.05, tmp)
            path = trace.find_xplane(tmp)
            (out / f"scoped_{app}.xplane.pb").write_bytes(
                _anonymised(Path(path).read_bytes()))
            calls = obs.calls(app)[-len(outputs):]
            text = _without_locations(obs.hlo(calls[0]))
            (out / f"scoped_{app}.hlo.txt").write_text(
                _anonymised(text.encode()).decode())
            s = trace.summarize(trace.load(path))
            by_scope = scopes.self_seconds(s.op_self_s,
                                           obs.scopes_of_hlo(text))
            print(json.dumps({
                "app": app, "jobs": len(outputs), "host_window_s": window_s,
                "programs": len({c.key for c in calls}),
                "counts": [_brief(obs.counts(c)) for c in calls],
                "window_s": s.window_s, "busy_s": s.busy_s,
                "scope_s": by_scope,
                "breakdown": trace.breakdown(s)}))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
