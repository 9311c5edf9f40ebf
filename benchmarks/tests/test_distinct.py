"""`distinct_60s`: the run-wise replay of its reference held to the
per-event rule on a hand-worked disordered example and on seeded ones, the
two copies of the per-event reference held to each other, the generator's
reproducibility and skew, the roofline's bytes, the readers of the window's
counters, and the cell rehearsed end to end. Not tier-1 (`JAX_PLATFORMS=cpu
python -m pytest benchmarks/tests -q`)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, REPO)

import registry  # noqa: E402
import roofline  # noqa: E402
import roofline_distinct  # noqa: E402
from tests.window_reference import DistinctTimeWindow as PlainCopy  # noqa: E402

reference = registry.load_module("references", "distinct_60s")
generator = registry.load_module("generators", "trades_zipf")
CONFIG = registry.load_json("configs", "distinct_60s")
PARAMS = {**CONFIG["inputs"]["cseEventStream"]["params"],
          "rows_per_frame": 131072}


def per_event(runs, width):
    w = reference.DistinctTimeWindow(width)
    return [[w.arrive(int(s), start + i) for i, s in enumerate(symbols)]
            for start, symbols in runs]


def test_the_reference_on_a_hand_worked_disordered_example():
    """Width 10. Runs of consecutive stamps, as frames of three producers
    arrive: 20.., 0.., 10.., 30... Symbols a=0, b=1, c=2, d=3."""
    runs = [(20, [0, 1]),      # clock 21; window {a@20, b@21}: 1, 2
            (0, [2, 2]),       # stale on arrival: c@0 stands (3); at c@1,
                               # clock still 21, the head a@20 is not due
                               # and holds c@0 behind it: 3
            (10, [3]),         # d@10: head a@20 due at 30, clock 21: 4
            (30, [0, 3])]      # a@30: clock 30 pops a@20 (due 30) but b@21
                               # (due 31) holds c, c, d: {b, c, d, a}: 4;
                               # d@31: clock 31 pops b, then c@0, c@1, d@10
                               # (all past due behind it): {a@30, d@31}: 2
    want = [[1, 2], [3, 3], [4], [4, 2]]
    assert per_event(runs, 10) == want
    replay = reference.Replay(10, 4)
    got = [replay.run(start, np.array(symbols), rows=True).tolist()
           for start, symbols in runs]
    assert got == want
    ends = reference.Replay(10, 4)
    assert [ends.run(start, np.array(symbols)) for start, symbols in runs] \
        == [rows[-1] for rows in want]


@pytest.mark.parametrize("seed", range(6))
def test_the_replay_equals_the_per_event_rule_on_seeded_disorder(seed):
    rng = np.random.default_rng(seed)
    width, rows, keys = 400, 50, 30
    frames = np.arange(40)
    # swap neighbours and throw frames a few places back, as four
    # closed-loop producers do; one frame arrives a whole window late
    for i in rng.choice(38, 12, replace=False):
        j = i + int(rng.integers(1, 4))
        frames[[i, min(j, 39)]] = frames[[min(j, 39), i]]
    frames = np.r_[frames[3:25], frames[0], frames[25:], frames[1:3]]
    runs = [(int(f) * 60, rng.integers(0, keys, rows)) for f in frames]
    want = per_event(runs, width)
    plain = PlainCopy(width)
    assert [[plain.arrive(int(s), start + i) for i, s in enumerate(symbols)]
            for start, symbols in runs] == want
    every, ends = reference.Replay(width, keys), reference.Replay(width, keys)
    for (start, symbols), rows_want in zip(runs, want):
        assert every.run(start, symbols, rows=True).tolist() == rows_want
        assert ends.run(start, symbols) == rows_want[-1]
    assert every.rows == len(plain.fifo)


def test_the_generator_is_a_function_of_its_arguments_alone():
    a = generator.columns(PARAMS, 3200000001, "cseEventStream", 2, 5)
    b = generator.columns(PARAMS, 3200000001, "cseEventStream", 2, 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    other = generator.columns(PARAMS, 3200000001, "cseEventStream", 2, 6)
    assert not np.array_equal(a["symbol"], other["symbol"])
    assert a["symbol"].min() >= 0 and a["symbol"].max() < PARAMS["keys"]


def test_the_skew_gives_about_29900_distinct_strings_a_frame():
    distinct = [np.unique(generator.columns(
        PARAMS, 11, "cseEventStream", p, slot)["symbol"]).size
        for p in range(2) for slot in range(3)]
    assert all(29000 < d < 30800 for d in distinct), distinct
    # the head is heavy: symbol 0 is about 1 row in 10
    head = generator.columns(PARAMS, 11, "cseEventStream", 0, 0)["symbol"]
    assert 0.08 < np.mean(head == 0) < 0.13


def test_the_rooflines_bytes():
    work = roofline_distinct.distinct_step(131072)
    assert work["bytes"] == 131072 * (34 + 32 + 32 + 16 + 16 + 26)
    least = roofline.least_seconds(work, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(131072 * 156 / 819e9)


def test_the_window_readers_read_the_programs_counters_and_nothing_else():
    def stats(steps, lanes, hwm, lost):
        return {"windows": {"distinct": {
            "capacity": 1000, "steps": steps, "out_lanes": lanes,
            "live_hwm": hwm, "ring_overflow": lost, "expiry_deferred": 0}}}
    run = {"stats0": stats(10, 1000, 880, 0), "stats1": stats(30, 3000, 900, 3),
           "trace": {"stats_open": stats(20, 2000, 910, 0),
                     "stats_close": stats(25, 2500, 890, 0)},
           "delivered": {"enter_ns": np.array([5, 15, 25, 99]),
                         "rows": np.array([20, 20, 20, 20])},
           "t0_ns": 10, "t_end_ns": 30}
    read = {name: registry.load_module("layer_metrics", name).read
            for name in ("window.fill_pct", "window.rows_lost",
                         "window.block_fill_pct")}
    assert read["window.fill_pct"](run) == pytest.approx(91.0)
    assert read["window.rows_lost"](run) == 3.0
    assert read["window.block_fill_pct"](run) == pytest.approx(20.0)
    parent = {**run, "stats0": {}, "stats1": {}, "trace": None}
    assert all(r(parent) is None for r in read.values())


def test_the_cell_rehearses_correct():
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
    assert set(last["metrics"]) == {"events_per_s", "setup_s"}
    assert detail["sample"]["sampled"] == 64
    assert 10000 <= detail["account"]["window_rows_hwm"] <= 11500
    assert detail["per_layer"]["window.rows_lost"] == 0.0
    assert detail["per_layer"]["window.block_fill_pct"] == pytest.approx(20.0)
