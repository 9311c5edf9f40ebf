"""`window.selector_lanes_pct`: the reader held to a recorded pair of
statistics, and `distinct_60s` rehearsed end to end with the selector in
rounds. Not tier-1 (`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests
-q`)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import registry  # noqa: E402

read = registry.load_module("layer_metrics", "window.selector_lanes_pct").read


def stats(out_lanes, selector_lanes=None):
    w = {"capacity": 1000, "steps": out_lanes // 640, "out_lanes": out_lanes}
    if selector_lanes is not None:
        w["selector_lanes"] = selector_lanes
    return {"windows": {"distinct": w}}


def test_the_reader_reads_rounds_over_the_chunks_lanes():
    # 64 steps of a 640-lane chunk: 40 ran two rounds of 128, 24 three
    run = {"stats0": stats(6400, 2560), "stats1": stats(
        6400 + 64 * 640, 2560 + 40 * 256 + 24 * 384)}
    assert read(run) == pytest.approx(100.0 * (40 * 256 + 24 * 384)
                                      / (64 * 640))


def test_the_one_call_reads_every_lane():
    assert read({"stats0": stats(640, 640), "stats1": stats(1280, 1280)}) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("run", [
    {"stats0": stats(6400), "stats1": stats(7040)},
    {"stats0": stats(6400), "stats1": stats(7040, 640)},
    {"stats0": {}, "stats1": {}},
    {"stats0": stats(640, 640), "stats1": stats(640, 640)},
], ids=["no-counter", "counter-only-after", "no-window", "no-steps"])
def test_the_reader_reads_nothing_where_there_is_nothing(run):
    assert read(run) is None


def test_the_cell_rehearses_with_the_selector_in_rounds():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "distinct_60s.saturate", "--seed", "3200000011", "--seconds", "2",
         "--trace", "0", "--rehearse"], cwd=REPO, text=True,
        capture_output=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    last, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["correct"] is True and last["failed"] == 0
    # expire 1024 over a 256-row batch: the selector runs in rounds
    assert 20.0 <= detail["per_layer"]["window.selector_lanes_pct"] < 100.0
