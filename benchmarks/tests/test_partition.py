"""`partition_1m`: the run-wise replay of its reference held to the
per-event rule on a hand-worked example and on seeded runs, the two copies
of the per-event reference held to each other, the reference's account and
sample on a hand-made run with known faults, the generator's
reproducibility and its bijection, the roofline's bytes, the readers of the
partition's counters, and the cell rehearsed end to end. Not tier-1
(`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`)."""

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

import record  # noqa: E402
import registry  # noqa: E402
import roofline  # noqa: E402
import roofline_partition  # noqa: E402
from tests.partition_reference import (  # noqa: E402
    keyed_window_aggregates as plain_copy)

reference = registry.load_module("references", "partition_1m")
generator = registry.load_module("generators", "temps")
CONFIG = registry.load_json("configs", "partition_1m")
PARAMS = {**CONFIG["inputs"]["TempStream"]["params"],
          "rows_per_frame": 131072}
ROWS, KEYS = 64, 12


def per_event(runs, length=10):
    w = reference.KeyedLengthWindows(length)
    return [[w.arrive(int(d), float(t)) for d, t in zip(devices, temps)]
            for devices, temps in runs]


def test_the_reference_on_a_hand_worked_example():
    """Length 2, devices a=0, b=1. Two runs; in the second `a` comes three
    times (more than the window) between two events of `b`."""
    runs = [(np.array([0, 1, 0]), np.array([5.0, 9.0, 3.0], np.float32)),
            (np.array([1, 0, 0, 0, 1]),
             np.array([1.0, 8.0, 2.0, 1.0, 0.5], np.float32))]
    # a: [5] [5,3] | [3,8] [8,2] [2,1];  b: [9] | [9,1] [1,.5]
    want = [[5.0, 9.0, 5.0], [9.0, 8.0, 8.0, 2.0, 1.0]]
    assert per_event(runs, 2) == want
    replay = reference.Replay(2, length=2)
    assert [replay.run(d, t).tolist() for d, t in runs] == want
    # skipped runs leave the state a run's rows are then taken from
    skipped = reference.Replay(2, length=2)
    skipped.skip(*runs[0])
    assert skipped.run(*runs[1]).tolist() == want[1]


@pytest.mark.parametrize("seed", range(6))
def test_the_replay_equals_the_per_event_rule_on_seeded_runs(seed):
    rng = np.random.default_rng(seed)
    runs = []
    for r in range(30):
        devices = rng.integers(0, KEYS, ROWS)
        if r % 7 == 3:
            devices[5:40] = 2  # one device far beyond its window in a run
        runs.append((devices, (rng.integers(640, 2561, ROWS) / 64.0)
                     .astype(np.float32)))
    want = per_event(runs)
    flat_d = np.concatenate([d for d, _ in runs]).tolist()
    flat_t = np.concatenate([t for _, t in runs]).tolist()
    plain, away = plain_copy(flat_d, flat_t, 10, "max")
    assert away == 0 and plain == [v for rows in want for v in rows]
    every, some = reference.Replay(KEYS), reference.Replay(KEYS)
    for i, ((devices, temps), rows_want) in enumerate(zip(runs, want)):
        assert every.run(devices, temps).tolist() == rows_want
        if i % 3:
            some.skip(devices, temps)
        else:
            assert some.run(devices, temps).tolist() == rows_want


def test_the_generator_is_a_function_of_its_arguments_alone():
    a = generator.columns(PARAMS, 36, "TempStream", 2, 5)
    b = generator.columns(PARAMS, 36, "TempStream", 2, 5)
    c = generator.columns(PARAMS, 36, "TempStream", 2, 6)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["device"], c["device"])
    # about 123,000 distinct devices a frame, a device a handful of times
    assert 121000 < np.unique(a["device"]).size < 125000
    assert np.bincount(a["device"]).max() <= 8
    # exact in float32, inside the stated range
    assert np.array_equal(a["temp"].astype(np.float32).astype(np.float64),
                          a["temp"])
    assert a["temp"].min() >= 10.0 and a["temp"].max() <= 40.0
    assert a["roomNo"].min() >= 0 and a["roomNo"].max() < 10000


def test_a_devices_id_is_a_seeded_bijection_of_its_rank():
    ranks = np.arange(1000000)
    ids = generator.device_ids(ranks, 36)
    assert ids.min() >= 0 and np.unique(ids).size == ranks.size
    assert not np.array_equal(ids, generator.device_ids(ranks, 37))
    # a serial number: ids of neighbouring ranks are far apart, and a
    # device's room is its own
    assert np.abs(np.diff(ids[:1000])).min() > 2 ** 40
    assert np.array_equal(generator.rooms_of(ranks[:50], 10000),
                          generator.rooms_of(ranks[:50], 10000))


def test_the_rooflines_bytes():
    work = roofline_partition.partition_step(131072, 10)
    assert work["bytes"] == 131072 * (34 + 12 + 40 + 32 + 8 + 34)
    least = roofline.least_seconds(work, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(131072 * 160 / 819e9)


def test_the_partition_readers_read_the_programs_counters_and_nothing_else():
    def stats(steps, lanes, keys, dropped, sync_ms):
        return {"partitions": {"partition1": {
            "capacity": 1000, "length": 10, "steps": steps,
            "out_lanes": lanes, "keys": keys, "keys_dropped": dropped,
            "stage_ms": {"drop_sync": {"total_ms": sync_ms}}}}}
    run = {"stats0": stats(10, 1000, 900, 0, 1.0),
           "stats1": stats(30, 3000, 940, 3, 5.0),
           "delivered": {"enter_ns": np.array([5, 15, 25, 99]),
                         "rows": np.array([80, 80, 80, 80])},
           "t0_ns": 10, "t_end_ns": 30,
           "device": {"platform": "cpu", "kind": "cpu"}}
    names = ("partition.keys_dropped", "partition.slot_fill_pct",
             "partition.block_fill_pct", "partition.drop_sync_ms",
             "partition_step_roofline")
    read = {n: registry.load_module("layer_metrics", n).read for n in names}
    assert read["partition.keys_dropped"](run) == 3.0
    assert read["partition.slot_fill_pct"](run) == pytest.approx(94.0)
    assert read["partition.block_fill_pct"](run) == pytest.approx(80.0)
    assert read["partition.drop_sync_ms"](run) == pytest.approx(0.2)
    assert read["partition_step_roofline"](run) is None  # no chip
    chip = {**run, "config": {"sizes": {"batch": 131072}},
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "reduced_trace": {"module_seconds": {
                "jit_step(123)": (0.160, 10), "jit__wire_pack(1)": (9.0, 9)}}}
    assert read["partition_step_roofline"](chip) == pytest.approx(
        100 * (131072 * 160 / 819e9) / 0.016)
    # a program without the section (the parent), traced or not
    parent = {**chip, "stats0": {}, "stats1": {}}
    assert all(r(parent) is None for r in read.values())


# ------------------------------------------ the account, on a hand-made run


class _Block:
    """What the reference reads of the program's ColumnarBlock."""

    def __init__(self, timestamps, columns):
        self.timestamps = np.asarray(timestamps, np.int64)
        self.columns = {k: np.asarray(v) for k, v in columns.items()}
        self.count = self.timestamps.size
        self.is_expired = np.zeros(self.count, bool)

    def column(self, name):
        return self.columns[name]


def _run(order=None, doctor=None, stats=None):
    """Two warm-up frames, then three rounds of two producers; one block a
    frame, in `order`, from the tests' copy of the per-event reference,
    `doctor`ed before the account sees it."""
    traffic = {"producers": 2, "pool": 2, "rows_per_frame": ROWS,
               "rehearse_rows_per_frame": ROWS, "params": {"keys": KEYS}}
    events = record.Events(
        registry.stream_plans(CONFIG, traffic, rehearse=True), 5, warm=2)
    order = order or list(range(8))
    keys, temps = [], []
    for f in order:
        cols = events.frame_columns(f)
        keys += cols["device"].tolist()
        temps += cols["temp"].astype(np.float32).tolist()
    maxima, _ = plain_copy(keys, temps, 10, "max")
    blocks = []
    for i, f in enumerate(order):
        cols = events.frame_columns(f)
        ts = f * ROWS + np.arange(ROWS)
        blocks.append([ts, {
            "timestamp": ts.copy(), "roomNo": cols["roomNo"].copy(),
            "deviceID": cols["deviceID"].copy(),
            "maxTemp": np.array(maxima[i * ROWS:(i + 1) * ROWS],
                                np.float64)}])
    if doctor:
        doctor(blocks)
    measured = list(range(2, 8))
    frames = record.merge_frame_logs([
        {"frame": [f for f in measured if f % 2 == p],
         "due_ns": [0] * 3, "send_ns": [0] * 3, "done_ns": [1] * 3,
         "status": [200] * 3, "accepted": [ROWS] * 3,
         "reconnects": [0] * 3} for p in range(2)], events)
    delivered = {"blocks": [_Block(ts, cols) for ts, cols in blocks]}
    delivered["rows"] = np.array([b.count for b in delivered["blocks"]])
    delivered["enter_ns"] = np.arange(len(blocks), dtype=np.int64) * 10
    return {
        "frames": frames, "events": events, "delivered": delivered,
        "config": CONFIG, "sent_extra": {0: ROWS, 1: ROWS},
        "stats_end": {
            "ingress_pipeline": {"TempStream": {"rows_in": 8 * ROWS}},
            "ingress_dropped": {}, "overflow": {},
            "partitions": {"partition1": {"keys": KEYS, "keys_dropped": 0}},
            **(stats or {})}}


@pytest.mark.parametrize("order", [None, [0, 1, 3, 2, 4, 6, 5, 7]],
                         ids=["straight", "producers_overtake"])
def test_a_clean_run_passes_in_the_order_the_rows_show(order):
    run = _run(order)
    out = reference.account(run)
    assert out["conserved"], out["failures"]
    assert out["failed"] == 0 and out["attempted"] == 6 * ROWS
    assert out["detail"]["runs"] == 8
    sample = reference.verify_sample(run, np.random.default_rng(0))
    assert sample == {"failures": [], "sampled": 8, "unit": "runs"}
    assert reference.completed(run, 0, 10 ** 9) == 6 * ROWS
    assert reference.expected_output_rows(run, range(8)) == 8 * ROWS


def _stale_maximum(blocks):
    blocks[5][1]["maxTemp"][7] -= 1 / 64  # one row, one grid step


def _wrong_device(blocks):
    blocks[3][1]["deviceID"][0] += 1


def _swapped_rows(blocks):
    for col in [blocks[4][0], *blocks[4][1].values()]:
        col[[10, 11]] = col[[11, 10]]


@pytest.mark.parametrize("doctor,where", [
    (_stale_maximum, "sample"), (_wrong_device, "sample"),
    (_swapped_rows, "account")], ids=lambda v: getattr(v, "__name__", v))
def test_a_run_with_a_fault_fails(doctor, where):
    run = _run(doctor=doctor)
    out = reference.account(run)
    sample = reference.verify_sample(run, np.random.default_rng(0))
    if where == "account":
        assert not out["conserved"] and out["failed"] > 0
        assert "rows_in_arrival_order_within_a_frame" in str(out["failures"])
    else:
        assert out["conserved"]
        assert sample["failures"], sample


@pytest.mark.parametrize("stats", [
    {"overflow": {"query:deviceMax.partition_keys_dropped": 9}},
    {"partitions": {"partition1": {"keys": KEYS, "keys_dropped": 9}}}],
    ids=["overflow", "section"])
def test_a_run_that_turned_keys_away_ends_at_the_counter(stats):
    run = _run(stats=stats)
    out = reference.account(run)
    assert not out["conserved"] and out["failed"] == out["attempted"]
    assert "found no slot" in out["failures"][0]
    assert reference.verify_sample(
        run, np.random.default_rng(0))["sampled"] == 0


# ----------------------------------------------------------- the manifest


def test_the_manifest_lists_the_cell_and_its_readers_find_their_files():
    man = registry.manifest()
    cell = registry.cell("partition_1m.saturate")
    assert cell["chips"] == 1 and cell["config"]["reduced"] == []
    assert [m["name"] for m in cell["end_to_end"]] == ["events_per_s",
                                                       "setup_s"]
    mine = {m["name"] for m in man["per_layer"]
            if m.get("workloads") == ["partition_1m.saturate"]}
    assert mine == {"partition_step_roofline", "partition.keys_dropped",
                    "partition.slot_fill_pct", "partition.block_fill_pct",
                    "partition.drop_sync_ms"}
    for m in cell["per_layer"]:
        assert registry.load_module("layer_metrics", m["name"]).read
    assert len(man["workloads"]) == 8 and len(man["configs"]) == 6
    assert all(w["chips"] == 1 for w in man["workloads"])


def test_the_cell_rehearses_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "partition_1m.saturate", "--seed", "3600000011", "--seconds", "2",
         "--trace", "0", "--rehearse"], cwd=REPO, text=True,
        capture_output=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    last, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"events_per_s", "setup_s"}
    assert detail["sample"]["sampled"] == 64
    assert detail["account"]["keys_held"] == {"partition1": 300}
    assert detail["per_layer"]["partition.keys_dropped"] == 0.0
    assert detail["per_layer"]["partition.block_fill_pct"] \
        == pytest.approx(100.0)
    assert detail["per_layer"]["partition.slot_fill_pct"] \
        == pytest.approx(100.0 * 300 / 4096)
