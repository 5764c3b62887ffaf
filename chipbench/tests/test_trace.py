"""The trace reduction, on synthetic traces with known answers and on a
small trace recorded on a TPU v5e (``data/small.xplane.pb``, written by
``record_trace.py``)."""
from pathlib import Path

import jax
import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _profile(devices, host):
    """XSpace text proto: ``devices`` = [[(name, start_ns, dur_ns)]],
    ``host`` = [(span name, start_ns, dur_ns)]."""
    planes = []
    for i, ops in enumerate(devices):
        names = sorted({n for n, _, _ in ops})
        meta = {n: k + 1 for k, n in enumerate(names)}
        evs = " ".join(_event(meta[n], s, d) for n, s, d in ops)
        md = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }}' for n, k in meta.items())
        planes.append(f'planes {{ id: {i + 2} name: "/device:TPU:{i}" '
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
                      f'{evs} }} {md} }}')
    names = sorted({n for n, _, _ in host})
    meta = {n: k + 1 for k, n in enumerate(names)}
    evs = " ".join(_event(meta[n], s, d) for n, s, d in host)
    md = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                  f'name: "{n}" }} }}' for n, k in meta.items())
    planes.append(f'planes {{ id: 1 name: "/host:CPU" lines {{ id: 1 '
                  f'name: "python" timestamp_ns: 0 {evs} }} {md} }}')
    return jax.profiler.ProfileData.from_text_proto("\n".join(planes))


def test_busy_idle_self_time_and_gaps():
    # window [0, 100) ns; device busy [10, 40) and [50, 62), a nested op
    # inside the first; a job span [6, 70) and the idle tail outside it
    prof = _profile(
        [[("while.1", 10, 30), ("fusion.2", 15, 10), ("scatter.3", 50, 12)]],
        [("bench:window", 0, 100), ("bench:job", 6, 64)])
    s = trace.summarize(prof)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == [pytest.approx(42e-9)]
    assert s.idle_pct == pytest.approx(58.0)
    assert s.op_self_s["while.1"] == pytest.approx(20e-9)
    assert s.op_self_s["fusion.2"] == pytest.approx(10e-9)
    assert s.exposed_collective_s == [0.0]
    # idle: [0,10) host, [40,50) job, [62,100) host (midpoint 81 > 70)
    assert s.idle_gaps[0] == ("host", pytest.approx(38e-9))
    assert sorted(g[0] for g in s.idle_gaps) == ["bench:job", "host", "host"]
    b = trace.breakdown(s)
    assert [n for n, _ in b["device_ops"]] == ["while.1", "scatter.3",
                                               "fusion.2"]


def test_exposed_collective_takes_each_device_alone():
    # device 0: all-gather [0, 50) overlapped by compute [20, 40): 30 exposed
    # device 1: all-reduce [10, 20) alone: 10 exposed; ops outside the window
    # are cut at its edges
    prof = _profile(
        [[("all-gather.1", 0, 50), ("fusion.1", 20, 20)],
         [("all-reduce.2", 10, 10), ("fusion.9", 90, 30)]],
        [("bench:window", 0, 100)])
    s = trace.summarize(prof)
    assert s.exposed_collective_s == [pytest.approx(30e-9),
                                      pytest.approx(10e-9)]
    assert s.busy_s == [pytest.approx(50e-9), pytest.approx(20e-9)]
    assert s.mean_busy_s == pytest.approx(35e-9)


def test_needs_one_window_span():
    prof = _profile([[("fusion.1", 0, 10)]], [("bench:job", 0, 10)])
    with pytest.raises(ValueError):
        trace.summarize(prof)


def test_interval_helpers():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 2), (4, 6)], []) == [(0, 2), (4, 6)]


def test_short_name_drops_layouts():
    op = ("%fusion.16 = f32[4096]{0:T(1024)S(1)} fusion(s32[65536]{0:T(1024)}"
          " %p.1), kind=kCustom")
    assert trace.short_name(op) == ("%fusion.16 = f32[4096] fusion(s32[65536]"
                                    " %p.1), kind=kCustom")
    assert len(trace.short_name(op * 10)) == 160


def test_recorded_chip_trace():
    """Three PageRank jobs at scale 12 (undirected, 131,072 arc slots) on
    one TPU v5e in a 61.8 ms window, reduced on the chip to the same
    numbers (record_trace.py's output). The source locations in the file
    name the checkout ``<checkout>/``."""
    s = trace.summarize(trace.load(str(DATA / "small.xplane.pb")))
    assert s.window_s == pytest.approx(0.061799579, abs=1e-9)
    assert s.busy_s == [pytest.approx(0.057316696, abs=1e-9)]
    assert s.idle_pct == pytest.approx(7.2539, abs=1e-3)
    assert s.exposed_collective_s == [0]
    # self times add up to the busy time: the while loop's body ops are
    # not counted twice
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s[0], rel=1e-9)
    ops = trace.breakdown(s)["device_ops"]
    # the segment-sum scatter into the 4,096 ranks, then the gather of the
    # 131,072 arc slots
    assert ops[0][0].startswith("%fusion.16 = f32[4096] fusion(s32[131072]")
    assert ops[0][1] == pytest.approx(0.029017005, abs=1e-9)
    assert ops[1][0].startswith("%fusion.15 = f32[131072] fusion(f32[4096]")
    # device idle between the jobs' programs, while the host is in a job
    assert [g[0] for g in s.idle_gaps[:4]] == ["bench:job"] * 4
    assert s.idle_gaps[0][1] == pytest.approx(0.001834607, abs=1e-9)
