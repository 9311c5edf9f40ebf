"""The reader of the feeder's starve-delivery counter
(`layer_metrics/ingress.starve_deliver_pct.py`, PR 27) on hand-made
statistics: a share of the window's deliveries, summed over the input
streams; `None`, not an error, on a parent commit's statistics, which have
no such counter. Not part of tier-1."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import registry  # noqa: E402

NAME = "ingress.starve_deliver_pct"


def _run(streams, stats0, stats1):
    return {"events": NS(plans=[{"stream": s} for s in streams]),
            "trace": None,
            "stats0": {"ingress_pipeline": stats0},
            "stats1": {"ingress_pipeline": stats1}}


def _pipe(delivered, overlapped, on_starve=None):
    out = {"batches_delivered": delivered, "batches_overlapped": overlapped}
    if on_starve is not None:
        out["batches_delivered_on_starve"] = on_starve
    return out


def _read(run):
    return registry.load_module("layer_metrics", NAME).read(run)


def test_the_share_is_a_delta_over_the_window():
    run = _run(["S"], {"S": _pipe(3, 1, 2)}, {"S": _pipe(213, 11, 200)})
    assert _read(run) == pytest.approx(100.0 * 198 / 210)


def test_two_pipelines_are_summed_before_the_share_is_taken():
    run = _run(["L", "R"],
               {"L": _pipe(10, 10, 0), "R": _pipe(4, 0, 4)},
               {"L": _pipe(110, 100, 10), "R": _pipe(24, 0, 24)})
    # 10 + 20 on starve of 100 + 20 delivered: not the mean of 10 % and 100 %
    assert _read(run) == pytest.approx(25.0)


@pytest.mark.parametrize("stats0, stats1", [
    ({"S": _pipe(3, 1)}, {"S": _pipe(213, 11)}),
    ({"S": _pipe(3, 1)}, {"S": _pipe(213, 11, 200)}),
    ({}, {}),
], ids=["the_parents_statistics", "counter_at_one_end_only", "no_pipeline"])
def test_nothing_to_read_is_none_and_not_an_error(stats0, stats1):
    assert _read(_run(["S"], stats0, stats1)) is None


def test_a_window_without_a_delivery_reads_none():
    run = _run(["S"], {"S": _pipe(5, 2, 3)}, {"S": _pipe(5, 2, 3)})
    assert _read(run) is None


def test_the_manifest_reports_it_in_the_paced_cells():
    (entry,) = [m for m in registry.manifest()["per_layer"]
                if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "ingress pipeline",
        "moves": "latency_p50_ms",
        "workloads": ["groupby_1m.paced", "filter_700.paced"]}
    for cell in entry["workloads"]:
        assert NAME in [m["name"] for m in registry.cell(cell)["per_layer"]]


def test_a_traced_paced_rehearsal_reports_the_share():
    """Through run.py, at toy size on the CPU (never a device number): the
    paced cell's result line carries the metric, and every frame's batch
    went on starve, one frame an interval with nothing behind it."""
    import json

    from test_harness import rehearse
    proc, lines = rehearse("groupby_1m.paced", "--trace", "1", seconds="3")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"][NAME] == {"value": 100.0, "unit": "%"}
    assert detail["per_layer"]["ingress.hold_ms"] < 5.0
    # the saturated cells do not report it: their line leaves it out
    proc, lines = rehearse("filter_700.saturate", "--trace", "1",
                           seconds="3")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert NAME not in result["metrics"]
    assert 0.0 <= detail["per_layer"][NAME] <= 100.0
