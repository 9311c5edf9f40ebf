"""trace_reduce.py against a recorded TPU trace and against hand-made ones.

`data/groupby_1m_v5e_trimmed.xplane.pb` is a real trace of
`groupby_1m.saturate` on the TPU v5e (my chip run, PR 22), trimmed so that
it can be committed: of the plane `/device:TPU:0` the lines `XLA Modules`
and `XLA Ops` with the events that lie wholly inside the first 230 ms after
the benchmark's opening marker (1,625 operations, 20 program executions),
event names cut to 160 characters, and of the host plane the opening marker
alone. The expected numbers below were computed from the same file by
another method (a sweep over event edges at picosecond resolution, straight
from the protobuf), not by the code under test.
"""

import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402

# jax's reader hands out whole nanoseconds, the sweep counted picoseconds:
# up to 1 ns an event, 1,625 events in 132 ms
REL = 2e-5
FIXTURE = os.path.join(HERE, "data", "groupby_1m_v5e_trimmed.xplane.pb")


def test_recorded_tpu_trace_reduces_to_the_swept_numbers():
    out = trace_reduce.reduce_file(FIXTURE)
    assert out["chips"] == 1
    # no closing marker in the trimmed file: the window is the device
    # events' own span
    assert out["window_s"] == pytest.approx(0.210802281328, rel=REL)
    assert out["busy_s"] == pytest.approx(0.131942966636, rel=REL)
    assert out["op_total_s"] == pytest.approx(0.131942966636, rel=REL)
    assert sum(c for _, c in out["op_seconds"].values()) == 1625
    gaps = out["gaps"]
    # 1,624 by the sweep; gaps under a nanosecond vanish in whole ns
    assert 1000 < len(gaps) <= 1624
    assert gaps[0][1] - gaps[0][0] == pytest.approx(26443855.078, rel=REL)
    assert all(a[1] - a[0] >= b[1] - b[0] for a, b in zip(gaps, gaps[1:]))
    assert sum(z - a for a, z in gaps) / 1e9 == pytest.approx(
        out["window_s"] - out["busy_s"], rel=REL)
    # the agg step is the costliest `jit_step` program, by far
    mods = out["module_seconds"]
    assert mods["jit_step(1564419988953549867)"] == [
        pytest.approx(0.090912032578, rel=REL), 1]
    assert mods["jit_step(17204486960417058911)"] == [
        pytest.approx(0.00006717875, rel=REL), 2]
    assert mods["jit__wire_pack(9080190594304454751)"][1] == 1
    name, (seconds, _) = max(out["op_seconds"].items(),
                             key=lambda kv: kv[1][0])
    assert name.startswith("%fusion.88 = (u32[151072]") and len(name) <= 96
    assert seconds == pytest.approx(0.027854023906, rel=REL)


def _profile(planes):
    def event(name, start, dur, stats=None):
        # stats arrive as (key, value) pairs
        return NS(name=name, start_ns=float(start), duration_ns=float(dur),
                  stats=list((stats or {}).items()))
    return NS(planes=[NS(name=pn, lines=[
        NS(name=ln, events=[event(*e) for e in evs])
        for ln, evs in lines.items()]) for pn, lines in planes.items()])


def test_markers_set_the_window_and_tie_the_clocks():
    prof = _profile({
        "/device:TPU:0": {
            "XLA Ops": [("a", 50, 100),     # cut by the opening marker
                        ("b", 200, 100), ("c", 250, 100),  # overlap: 200-350
                        ("d", 900, 300)],   # cut by the closing marker
            "XLA Modules": [("jit_step(1)", 50, 300),   # cut: not counted
                            ("jit_step(2)", 400, 100)],
            "Async XLA Ops": [("ignored", 0, 5000)],
        },
        "/host:CPU": {"python": [("bench_trace_open", 100, 1),
                                 ("bench_trace_close", 1000, 1)]},
    })
    out = trace_reduce.reduce_profile(prof, mono_open_ns=5100)
    assert out["window_s"] == pytest.approx(900e-9)
    # [100,150] + [200,350] + [900,1000]
    assert out["busy_s"] == pytest.approx(300e-9)
    assert out["op_total_s"] == pytest.approx(350e-9)  # b and c both count
    assert out["op_seconds"]["a"] == [pytest.approx(50e-9), 1]
    assert out["module_seconds"] == {"jit_step(2)": [pytest.approx(100e-9),
                                                     1]}
    # trace clock is 5000 ns behind the monotonic clock; gaps come back on
    # the monotonic one, longest first
    assert out["clock_offset_ns"] == -5000
    assert out["gaps"] == [(5350, 5900), (5150, 5200)]


def test_busy_is_averaged_over_the_chips_used():
    prof = _profile({
        "/device:TPU:0": {"XLA Ops": [("x", 0, 100)]},
        "/device:TPU:1": {"XLA Ops": [("x", 0, 50)]},
        "/device:TPU:2": {"XLA Ops": []},  # not used: not counted
        "/host:CPU": {"python": [("bench_trace_open", 0, 1),
                                 ("bench_trace_close", 200, 1)]},
    })
    out = trace_reduce.reduce_profile(prof)
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx(75e-9)
    assert out["op_seconds"]["x"] == [pytest.approx(75e-9), 2]


def test_cpu_rehearsal_reads_host_events_that_name_an_hlo_module():
    prof = _profile({
        "/host:CPU": {
            "tf_XLAPjRtCpuClient/1": [
                ("dot.1", 10, 30, {"hlo_module": "jit_f"}),
                ("end: dot.1", 40, 1, {"hlo_module": "jit_f"}),
                ("ThreadpoolListener::Record", 12, 0)],
            "python": [("PjitFunction(f)", 0, 100)],
        },
    })
    out = trace_reduce.reduce_profile(prof, host_ops=True)
    assert out["chips"] == 1 and out["busy_s"] == pytest.approx(30e-9)
    # only the rehearsal asks for that: a measured run finds no chip here
    assert trace_reduce.reduce_profile(prof)["chips"] == 0


def test_an_empty_trace_reduces_to_nothing():
    out = trace_reduce.reduce_profile(_profile({"/host:CPU": {"python": []}}))
    assert out == {"chips": 0, "window_s": 0.0, "busy_s": 0.0}


def test_union_and_gaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == \
        [(0, 4), (5, 7)]
    assert trace_reduce.gaps([(0, 4), (5, 7)], 0, 10) == [(4, 5), (7, 10)]
    assert trace_reduce.gaps([], 2, 3) == [(2, 3)]
