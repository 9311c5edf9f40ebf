"""`registry_1m`: the configuration's sizes and what it assumes, the two
copies of the per-event reference held to each other, the reference's
account and sample on hand-made runs (one clean in two serialisations, and
runs with planted faults, among them a row enriched from a registration its
prefix does not hold), the generator, the rooflines' bytes, the seven new
readers at rehearsal sizes, the manifest, and the cell rehearsed end to end.
Not tier-1 (`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`)."""

import inspect
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
import roofline_table  # noqa: E402
from tests import registry_reference as plain_copy  # noqa: E402

reference = registry.load_module("references", "registry_1m")
generator = registry.load_module("generators", "registrations")
CONFIG = registry.load_json("configs", "registry_1m")
TRAFFIC = registry.load_json("traffic", "saturate_1to4")
CELL = "registry_1m.saturate_1to4"
KEYS, R_DEV, R_TEMP = 12, 8, 16
TYPES = CONFIG["inputs"]["DeviceStream"]["params"]["types"]


def test_the_configuration_holds_a_million_devices_in_a_2_20_row_table():
    sizes = CONFIG["sizes"]
    assert (sizes["batch"], sizes["table_capacity"]) == (131072, 1 << 20)
    keys = {s: d["params"]["keys"] for s, d in CONFIG["inputs"].items()}
    assert keys == {"DeviceStream": 1000000, "TempStream": 1000000}
    assert 100 * 1000000 / sizes["table_capacity"] == pytest.approx(95.37,
                                                                     abs=0.01)
    # the warm-up registers every device before the window opens
    rows = TRAFFIC["streams"]["DeviceStream"]["rows_per_frame"]
    assert CONFIG["warm_frames"] * rows >= 1000000
    assert list(CONFIG["inputs"]) == ["DeviceStream", "TempStream"]
    assert CONFIG["reduced"] == []
    assumed = " ".join(CONFIG["assumed"])
    for said in ("from memory", "per device", "'server-room', 'office', "
                 "'lab' and 'storage'", "`regStamp`", "6 %"):
        assert said in assumed, said
    app = "\n".join(CONFIG["app"])
    assert "@capacity(rows = '{table_capacity}')" in app
    assert "having roomType == 'server-room'" in app


def test_the_two_copies_of_the_reference_agree():
    assert inspect.getsource(reference.Registry) \
        == inspect.getsource(plain_copy.Registry)
    rng = np.random.default_rng(5)
    a, b = reference.Registry(), plain_copy.Registry()
    for i in range(500):
        d, kind = int(rng.integers(0, 20)), TYPES[int(rng.integers(0, 4))]
        if rng.random() < 0.25:
            a.upsert(d, d % 7, kind, i)
            b.upsert_many([d], [(d % 7, kind, i)])
        elif rng.random() < 0.3:
            a.upsert_many([d], [(d % 7, kind, i)])
            b.upsert(d, d % 7, kind, i)
        elif rng.random() < 0.5:
            a.insert(d, d % 7, kind, i)
            b.insert(d, d % 7, kind, i)
        assert a.enrich(d, 1.5, i) == b.enrich(d, 1.5, i)
    assert a.rows == b.rows and a.duplicates == b.duplicates


def test_the_generator_registers_every_device_once_in_the_warm_up():
    params = {**CONFIG["inputs"]["DeviceStream"]["params"], "keys": 100,
              "rows_per_frame": 32, "warm_producer": 1}
    ranks = np.concatenate([generator.columns(params, 7, "DeviceStream", 1,
                                              slot)["device"]
                            for slot in range(4)])
    assert ranks[:100].tolist() == list(range(100))
    again = generator.columns(params, 7, "DeviceStream", 0, 3)
    same = generator.columns(params, 7, "DeviceStream", 0, 3)
    assert all(np.array_equal(again[k], same[k]) for k in again)
    temps = registry.load_module("generators", "temps")
    assert np.array_equal(again["deviceID"],
                          temps.device_ids(again["device"], 7))
    assert np.array_equal(again["roomNo"],
                          temps.rooms_of(again["device"], params["rooms"]))


def test_the_rooflines_bytes():
    assert roofline_table.table_write(32768)["bytes"] == 68 * 32768
    assert roofline_table.table_join(131072, 0.25)["bytes"] \
        == 61 * 131072
    least = roofline.least_seconds(roofline_table.table_join(131072, 0.25),
                                   "TPU v5 lite")
    assert least["bound"] == "memory"


def test_the_new_readers_read_the_programs_counters_and_nothing_else():
    def stats(probes, hits, dropped, rows):
        return {"tables": {"DeviceTable": {
            "capacity": 1000, "rows": rows, "probes": probes, "hits": hits,
            "dropped": dropped, "inserted": rows, "updated": 0,
            "duplicates": 0, "steps": 1}}}

    events = type("E", (), {"plans": [{"rows": 32768}, {"rows": 131072}]})
    run = {"stats0": stats(1000, 990, 0, 900), "stats1": stats(3000, 2980,
                                                                2, 950),
           "delivered": {"enter_ns": np.array([5, 15, 25, 99]),
                         "rows": np.array([100, 100, 100, 100])},
           "t0_ns": 10, "t_end_ns": 30, "events": events,
           "device": {"platform": "cpu", "kind": "cpu"}}
    names = ("table.write_ms", "stage.table_ms", "table_write_roofline",
             "table_join_roofline", "table.fill_pct", "table.rows_dropped",
             "table.hit_pct")
    read = {n: registry.load_module("layer_metrics", n).read for n in names}
    assert read["table.fill_pct"](run) == pytest.approx(95.0)
    assert read["table.rows_dropped"](run) == 2.0
    assert read["table.hit_pct"](run) == pytest.approx(99.5)
    for n in ("table.write_ms", "stage.table_ms", "table_write_roofline",
              "table_join_roofline"):
        assert read[n](run) is None  # no chip
    chip = {**run, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "reduced_trace": {"module_seconds": {
                "jit_table_write_DeviceTable(7)": (0.040, 10),
                "jit_join_probe_left(3)": (0.090, 30),
                "jit_step(1)": (5.0, 9)}}}
    assert read["table.write_ms"](chip) == pytest.approx(4.0)
    assert read["table_write_roofline"](chip) == pytest.approx(
        100 * (68 * 32768 / 819e9) / 0.004)
    share = 200 / 2000  # rows delivered in the window over probes
    assert read["table_join_roofline"](chip) == pytest.approx(
        100 * ((24 + 12 + 16 + 36 * share) * 131072 / 819e9) / 0.003)
    # a program without the section or the programs (the parent)
    parent = {**chip, "stats0": {}, "stats1": {},
              "reduced_trace": {"module_seconds": {"jit_step(1)": (5.0, 9)}}}
    assert all(r(parent) is None for r in read.values())


# ------------------------------------------ the account, on a hand-made run


class _Block:
    """What the reference reads of the program's ColumnarBlock."""

    def __init__(self, rows):
        self.timestamps = np.array([r[4] for r in rows], np.int64)
        self.columns = {
            "deviceID": np.array([r[0] for r in rows], np.int64),
            "roomNo": np.array([r[1] for r in rows], np.int32),
            "roomType": np.array([TYPES.index(r[2]) + 100 for r in rows],
                                 np.int32),
            "temp": np.array([r[3] for r in rows], np.float32),
            "timestamp": np.array([r[4] for r in rows], np.int64),
            "regStamp": np.array([r[5] for r in rows], np.int64)}
        self.strings_of = [r[2] for r in rows]
        self.count = len(rows)
        self.is_expired = np.zeros(self.count, bool)

    def column(self, name):
        return self.columns[name]


def _events():
    traffic = {**TRAFFIC, "rehearse_rows_per_frame": R_TEMP,
               "params": {"keys": KEYS},
               "streams": {"DeviceStream": {
                   **TRAFFIC["streams"]["DeviceStream"],
                   "rehearse_rows_per_frame": R_DEV}},
               "producers": 2}
    return record.Events(
        registry.stream_plans(CONFIG, traffic, rehearse=True), 5, warm=2)


def _run(order, doctor=None):
    """Frames in the engine's `order` (registration and reading frames by
    number); one block a reading frame, from the tests' copy of the
    per-event reference at that point, `doctor`ed before the account."""
    events = _events()
    stride = events.stride
    reg = plain_copy.Registry()
    blocks, sent_regs, sent_reads = [], 0, 0
    for f in order:
        s = events.source(f)[0]
        cols = events.frame_columns(f)
        n = events.plan_of(f)["rows"]
        if s == reference.DEVICES:
            for j in range(n):
                reg.upsert(int(cols["deviceID"][j]), int(cols["roomNo"][j]),
                           TYPES[int(cols["type_code"][j])], f * stride + j)
            sent_regs += n
            continue
        sent_reads += n
        rows = [r for r in (reg.enrich(
            int(cols["deviceID"][j]), float(np.float32(cols["temp"][j])),
            f * stride + j) for j in range(n)) if r is not None]
        blocks.append(rows)
    if doctor:
        doctor(blocks, events)
    measured = [f for f in order if f >= events.warm_total]
    logs = []
    for index in range(events.producers):
        mine = [f for f in measured
                if (f - events.warm_total) % events.producers == index]
        rows = events.plans[events.flat[index][0]]["rows"]
        logs.append({"frame": mine, "due_ns": [0] * len(mine),
                     "send_ns": [0] * len(mine), "done_ns": [1] * len(mine),
                     "status": [200] * len(mine),
                     "accepted": [rows] * len(mine),
                     "reconnects": [0] * len(mine)})
    delivered = {"blocks": [_Block(rows) for rows in blocks]}
    delivered["rows"] = np.array([b.count for b in delivered["blocks"]])
    delivered["enter_ns"] = np.arange(len(blocks), dtype=np.int64) * 10
    warm = {f: events.plan_of(f)["rows"] for f in order
            if f < events.warm_total}
    return {
        "frames": record.merge_frame_logs(logs, events), "events": events,
        "delivered": delivered, "config": CONFIG, "sent_extra": warm,
        "stats_end": {
            "ingress_pipeline": {"DeviceStream": {"rows_in": sent_regs},
                                 "TempStream": {"rows_in": sent_reads}},
            "ingress_dropped": {}, "overflow": {},
            "tables": {"DeviceTable": {
                "rows": KEYS, "inserted": KEYS,
                "updated": sent_regs - KEYS, "dropped": 0, "duplicates": 0,
                "probes": sent_reads, "hits": sent_reads}}}}


# warm: registrations 0, 1; readings 2, 3. Then rounds of (registration,
# reading, reading): frames 4 + 3k + {0, 1, 2}
STRAIGHT = list(range(4 + 3 * 4))
OVERTAKE = [0, 1, 2, 3, 5, 4, 6, 8, 9, 7, 11, 10, 12, 13, 14, 15]


@pytest.mark.parametrize("order", [STRAIGHT, OVERTAKE],
                         ids=["straight", "readings_overtake"])
def test_a_clean_run_passes_in_the_order_the_rows_show(order):
    run = _run(order)
    out = reference.account(run)
    assert out["conserved"], out["failures"]
    assert out["failed"] == 0
    assert reference.completed(run, 0, 10 ** 9) == 8 * R_TEMP + _held(run)
    assert out["detail"]["registration_share"] == pytest.approx(
        (6 * R_DEV) / (6 * R_DEV + 10 * R_TEMP))


def _held(run):
    rep = reference.replay(run)
    return max(p for p in rep["prefix"] if p is not None) - rep["regs"].warm


def test_the_sample_compares_every_column_with_the_per_event_loop(
        monkeypatch):
    run = _run(STRAIGHT)
    monkeypatch.setattr(record, "gather", lambda segs, numeric, strings: {
        "ts": segs[0][0].timestamps,
        **{n: segs[0][0].column(n) for n in numeric},
        "roomType": segs[0][0].strings_of})
    sample = reference.verify_sample(run, np.random.default_rng(0))
    assert sample == {"failures": [], "sampled": 10, "unit": "blocks"}


def _stale_registration(blocks, events):
    """A row enriched from an older registration of its device than the
    one the block's prefix holds: the prefix that explains the other rows
    must hold the newer one."""
    for b in range(len(blocks) - 1, 0, -1):
        rows = blocks[b]
        for i, r in enumerate(rows):
            stamps = [s for s in _stamps_of(events, r[0]) if s < r[5]]
            if stamps:
                rows[i] = (*r[:5], stamps[-1])
                return
    raise AssertionError("no row to doctor")


def _stamps_of(events, device_id):
    out = []
    for f in range(4 + 3 * 4):
        if events.source(f)[0] != reference.DEVICES:
            continue
        cols = events.frame_columns(f)
        out += [f * events.stride + j
                for j in np.nonzero(cols["deviceID"] == device_id)[0]]
    return sorted(out)


def _future_registration(blocks, events):
    """A row enriched from a registration no prefix yet holds."""
    for rows in blocks[:-1]:
        for i, r in enumerate(rows):
            later = [x for x in _stamps_of(events, r[0]) if x > r[5]]
            if later:
                rows[i] = (*r[:5], later[-1])
                return
    raise AssertionError("no row to doctor")


def _missing_row(blocks, events):
    """A reading in a server room with no row, whatever came after."""
    rows = blocks[-1]
    for i, r in enumerate(rows):
        if not [x for x in _stamps_of(events, r[0]) if x > r[5]]:
            del rows[i]
            return
    raise AssertionError("no row to doctor")


def _wrong_temp(blocks, events):
    r = blocks[3][0]
    blocks[3][0] = (*r[:3], r[3] + 1 / 64, *r[4:])


@pytest.mark.parametrize("doctor", [_stale_registration,
                                    _future_registration, _missing_row,
                                    _wrong_temp],
                         ids=lambda d: d.__name__)
def test_a_run_with_a_fault_fails(doctor):
    run = _run(STRAIGHT, doctor)
    out = reference.account(run)
    assert not out["conserved"] and out["failed"] > 0, out
    assert "every_block_explained_by_a_prefix" in str(out["failures"])


def test_a_run_whose_table_dropped_rows_ends_at_the_counter():
    run = _run(STRAIGHT)
    run["stats_end"]["tables"]["DeviceTable"]["dropped"] = 3
    out = reference.account(run)
    assert not out["conserved"] and out["failed"] == out["attempted"]
    assert "table_dropped_zero" in out["failures"][0]


# ----------------------------------------------------------- the manifest


def test_the_manifest_lists_the_cell_wherever_its_metrics_say():
    man = registry.manifest()
    cell = registry.cell(CELL)
    assert cell["chips"] == 1 and cell["config"]["reduced"] == []
    assert cell["traffic"]["streams"]["DeviceStream"]["producers"] == 1
    assert [m["name"] for m in cell["end_to_end"]] == ["events_per_s",
                                                       "setup_s"]
    mine = {m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"table.write_ms", "stage.table_ms",
                    "table_write_roofline", "table_join_roofline",
                    "table.fill_pct", "table.rows_dropped", "table.hit_pct"}
    listed = {m["name"] for m in cell["per_layer"]}
    for name in ("device.step_ms", "state.peak_bytes", "join.step_ms",
                 "join.block_fill_pct", "front.post_ms", "native.decode_ms",
                 "ingress.h2d_ms", "dispatch.work_ms", "stage.filter_ms",
                 "stage.unattributed_pct"):
        assert name in listed, name
    for m in cell["per_layer"]:
        assert registry.load_module("layer_metrics", m["name"]).read
    config = next(c for c in man["configs"] if c["name"] == "registry_1m")
    assert config["file"] == "benchmarks/configs/registry_1m.json"


def test_the_cell_rehearses_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3600000021", "--seconds", "2", "--trace", "0",
         "--rehearse"], cwd=REPO, text=True, capture_output=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    last, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"events_per_s", "setup_s"}
    assert detail["sample"]["sampled"] == 64
    table = detail["account"]["table"]
    assert table["rows"] == 300 and table["dropped"] == 0
    assert detail["per_layer"]["table.rows_dropped"] == 0.0
    assert detail["per_layer"]["table.hit_pct"] == pytest.approx(100.0)
    assert detail["per_layer"]["table.fill_pct"] \
        == pytest.approx(100.0 * 300 / 1024)
