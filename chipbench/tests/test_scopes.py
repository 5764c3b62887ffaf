"""The scope reduction (``chipbench/scopes.py``) on scoped traces recorded
on a TPU v5e (``data/scoped_*``, written by ``record_scoped_trace.py``),
with the compiled HLO of the program each window ran."""
from pathlib import Path

import pytest

from chipbench import scopes, trace
from repro import obs

DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("app,busy,expected", [
    # three PageRank jobs of 9 iterations at scale 12 (131,072 arc slots)
    ("pagerank", 0.057473595, {obs.GATHER: 0.025263757, obs.REDUCE:
                               0.029017258, obs.OUT_DEGREE: 0.003131251}),
    # two SSSP queries of 12 and 11 rounds on the same graph
    ("sssp", 0.064561862, {obs.GATHER: 0.021499888, obs.FRONTIER:
                           0.018949005, obs.REDUCE: 0.024043074,
                           obs.COUNTERS: 0.000036979}),
])
def test_recorded_scoped_trace(app, busy, expected):
    """Every device op of the recorded window is an instruction of the
    program's compiled HLO, and the scopes hold at least 99% of the busy
    time (99.89% and 99.95%, as reduced on the chip)."""
    s = trace.summarize(trace.load(str(DATA / f"scoped_{app}.xplane.pb")))
    scope_of = obs.scopes_of_hlo(
        (DATA / f"scoped_{app}.hlo.txt").read_text())
    assert s.busy_s == [pytest.approx(busy, abs=1e-9)]
    assert all(scopes.instruction(op) in scope_of for op in s.op_self_s)
    by_scope = scopes.self_seconds(s.op_self_s, scope_of)
    assert sum(by_scope.values()) == pytest.approx(busy, rel=1e-9)
    unscoped = by_scope.pop(scopes.UNSCOPED)
    assert unscoped <= 0.01 * busy
    assert by_scope == pytest.approx(expected, abs=1e-9)
