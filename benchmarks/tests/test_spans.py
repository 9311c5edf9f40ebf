"""The readers of the program's stage spans (spans.py and the seventeen
`layer_metrics/` files of PR 24): on hand-made statistics and gaps, on a
parent commit's statistics (no such cell: None, not an error), and on the
trace of a rehearsal, where the feeder's four states must lie on the clock
of the device's idle gaps and cover them. Not part of tier-1."""

import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import registry  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402
from test_harness import rehearse  # noqa: E402

NEW = [m["name"] for m in registry.manifest()["per_layer"]
       if m["source"] == "program_span"]
STATES = ("fill", "h2d", "lock_wait", "dispatch")


def _cell(total_ms, batches, cpu_ms=None):
    cell = {"total_ms": total_ms, "batches": batches,
            "mean_ms": total_ms / batches if batches else 0.0}
    if cpu_ms is not None:
        cell["cpu_ms"] = cpu_ms
    return cell


def _run(stage0, stage1, back0=None, back1=None):
    def stats(stage, back):
        out = {"ingress_pipeline": {"S": {"stage_ms": stage}}}
        if back is not None:
            out["readback"] = {"stage_ms": back, "submitted": 0,
                               "delivered": 0}
        return out
    return {"events": NS(plans=[{"stream": "S"}]), "trace": None,
            "trace_dir": os.path.join(HERE, "no_such_dir"),
            "stats0": stats(stage0, back0), "stats1": stats(stage1, back1)}


def test_the_manifest_names_the_seventeen():
    assert len(NEW) == 17 and len(set(NEW)) == 17


def test_cpu_and_readback_means_are_deltas_over_the_window():
    run = _run({"h2d": _cell(10.0, 2, 4.0)}, {"h2d": _cell(70.0, 12, 9.0)},
               {"fetch": _cell(5.0, 1)}, {"fetch": _cell(35.0, 11)})
    assert spans.stage_cpu_mean_ms(run, "h2d") == pytest.approx(0.5)
    assert spans.readback_mean_ms(run, "fetch") == pytest.approx(3.0)
    assert registry.load_module("layer_metrics", "ingress.h2d_cpu_ms").read(
        run) == pytest.approx(0.5)
    assert registry.load_module("layer_metrics", "readback.fetch_ms").read(
        run) == pytest.approx(3.0)
    # nothing delivered in the window: no mean
    assert spans.readback_mean_ms(
        _run({}, {}, {"fetch": _cell(5.0, 1)}, {"fetch": _cell(5.0, 1)}),
        "fetch") is None


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_parent_commits_statistics(name):
    """The program before PR 24: four cells, no `cpu_ms`, no `readback`, no
    `siddhi.*` event. The reader returns None and does not raise."""
    old = {k: _cell(3.0, 2) for k in ("decode", "intern", "h2d", "device")}
    new = {k: _cell(9.0, 5) for k in old}
    run = _run(old, new)
    assert registry.load_module("layer_metrics", name).read(run) is None
    # traced, with gaps, but a trace that holds no span of the program's
    run.update(trace={"open_ns": 0}, host_spans={},
               reduced_trace={"gaps": [(0, 1_000_000)],
                              "clock_offset_ns": 0})
    assert registry.load_module("layer_metrics", name).read(run) is None


def test_idle_share_lays_long_gaps_to_the_feeders_states():
    run = _run({}, {})
    run["reduced_trace"] = {"gaps": [(0, 1_000_000), (2_000_000, 3_000_000),
                                     (5_000_000, 5_050_000)]}  # one short
    run["host_spans"] = {
        "siddhi.feeder.fill": [(0, 400_000), (2_900_000, 5_040_000)],
        "siddhi.feeder.h2d": [(400_000, 700_000)],
        "siddhi.feeder.lock_wait": [(1_900_000, 2_500_000)],
        "siddhi.readback.fetch": [(0, 3_000_000)]}
    assert spans.idle_share_pct(run, "fill") == pytest.approx(25.0)
    assert spans.idle_share_pct(run, "h2d") == pytest.approx(15.0)
    assert spans.idle_share_pct(run, "lock_wait") == pytest.approx(25.0)
    assert spans.idle_share_pct(run, "dispatch") == pytest.approx(0.0)
    assert registry.load_module(
        "layer_metrics", "idle.feeder_h2d_pct").read(run) \
        == pytest.approx(15.0)


def test_feeder_spans_lie_on_the_gaps_clock_and_cover_them():
    cell = "groupby_1m.paced"
    proc, lines = rehearse(cell, "--trace", "1", seconds="4")
    assert proc.returncode == 0, proc.stderr[-2000:]
    detail = json.loads(lines[-2])
    # the run's own readers: every one of the seventeen gave a number here
    assert all(detail["per_layer"][name] is not None for name in NEW), \
        detail["per_layer"]
    shares = [detail["per_layer"][f"idle.feeder_{s}_pct"] for s in STATES]
    assert 90.0 <= sum(shares) <= 101.0, shares
    # and again from the trace it left, on the trace's own clock
    trace_dir = os.path.join(BENCH, "out", "trace", cell)
    path = trace_reduce.newest_xplane(trace_dir)
    run = {"trace": {"open_ns": None}, "trace_dir": trace_dir,
           "reduced_trace": trace_reduce.reduce_file(path, host_ops=True)}
    found = spans.host_spans(run)
    profile = trace_reduce.load(path)
    lo = trace_reduce.find_marker(profile, trace_reduce.MARK_OPEN)
    hi = trace_reduce.find_marker(profile, trace_reduce.MARK_CLOSE)
    for state in STATES:
        mine = found["siddhi.feeder." + state]
        assert len(mine) >= 3, state
        # a span is recorded whole: the session opens a moment before the
        # first marker and closes a moment after the second
        inside = [lo <= a and z <= hi for a, z in mine]
        assert sum(inside) >= len(mine) - 2, state
        assert all(lo - 1e9 <= a and z <= hi + 1e9 for a, z in mine), state
    again = [spans.idle_share_pct(run, s) for s in STATES]
    assert again == pytest.approx(shares, abs=1.0)
