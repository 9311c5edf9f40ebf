"""The benchmark's own tests: run by hand and in the CPU rehearsal
(`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`), not part of
tier-1. The cells run end to end at toy size with `--rehearse`; a number
such a run prints is never a device metric."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, REPO)

import loadgen  # noqa: E402
import metrics  # noqa: E402
import record  # noqa: E402
import registry  # noqa: E402
import roofline  # noqa: E402
import sxf1  # noqa: E402

MANIFEST = registry.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def rehearse(cell, *extra, cwd=REPO, seconds="2"):
    proc = subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "7", "--seconds", seconds,
         "--rehearse", *extra], cwd=cwd, text=True, capture_output=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = proc.stdout.strip().splitlines()
    return proc, lines


# ------------------------------------------------------------ the contract


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_at_toy_size(cell):
    proc, lines = rehearse(cell, "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"  # a rehearsal says so
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(last["metrics"]) == want and "setup_s" in want
    for m in MANIFEST["end_to_end"]:
        if m["name"] in want:
            got = last["metrics"][m["name"]]
            assert got["unit"] == m["unit"] and got["value"] > 0
    detail = json.loads(lines[-2])
    assert all(detail["checks"].values()) and detail["sample"]["sampled"] > 0
    assert detail["window_retraces"] == 0 and not detail["window_programs"]


def test_traced_run_reports_per_layer_metrics_and_breakdown():
    proc, lines = rehearse("groupby_1m.paced", "--trace", "1", seconds="4")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert last["device"]["busy_s"] > 0
    assert last["device"]["window_s"] > last["device"]["busy_s"]
    want = {m["name"] for m in MANIFEST["per_layer"]
            if "groupby_1m.paced" in m.get("workloads", ["groupby_1m.paced"])}
    # nothing in the CPU's cache; and no device metric from a CPU run
    assert want - set(last["metrics"]) <= {"setup.cache_hit_share"}
    assert set(last["metrics"]) <= want
    assert last["metrics"]["dispatch.compiles_in_window"]["value"] == 0
    # the detail line shows every reader's number, this cell's or not
    detail = json.loads(lines[-2])
    assert detail["per_layer"]["ingress.h2d_ms"] > 0
    assert detail["per_layer"]["device.step_ms"] is None
    assert detail["per_layer"]["agg_step_roofline"] is None
    assert len(detail["events_per_s_by_quarter"]) == 4
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(last["breakdown"]["device_ops"]) <= 10
    assert 0 < len(last["breakdown"]["idle_gaps"]) <= 10


def test_refuses_to_measure_without_a_chip():
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=REPO,
        text=True, capture_output=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "requires a TPU" in proc.stderr


def test_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.
                    ignore_patterns("out", "__pycache__", ".pytest_cache"))
    proc, _ = rehearse(CELLS[0], "--trace", "0", cwd=str(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_manifest_names_units_and_files():
    man = MANIFEST
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[k]]
    assert all(NAME.match(n) for n in names)
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert os.path.exists(os.path.join(
            BENCH, "end_to_end", m["name"] + ".py"))
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in man["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        body = json.load(open(os.path.join(REPO, c["file"])))
        assert {"source", "assumed", "reduced", "guarantees"} <= set(body)
        assert body["reduced"] == c["reduced"]
    pairs = set()
    for w in man["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        pairs.add((w["config"], w["traffic"]))
        cell = registry.cell(w["name"])  # every named file is there
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        # a per-layer metric is reported only where the metric it moves is
        assert all(m["moves"] in reported for m in cell["per_layer"])
    assert len(pairs) == len(man["workloads"])
    assert len(json.dumps(man)) < 64 * 1024


# ---------------------------------------------------------- the arithmetic


PARAMS = {"keys": 200, "price_lo": 1, "price_hi": 4000, "price_step": 0.25,
          "volume_lo": 1, "volume_hi": 1000, "rows_per_frame": 512}


def _plan(stream="TradeStream", producers=1, pool=2, rows=4, **params):
    return {"stream": stream, "generator": "trades", "producers": producers,
            "pool": pool, "rows": rows,
            "params": {**PARAMS, **params, "rows_per_frame": rows}}


def _frames(due, send, done, status=None):
    n = len(due)
    return record.merge_frame_logs([{
        "frame": list(range(3, 3 + n)), "due_ns": due, "send_ns": send,
        "done_ns": done, "status": status or [200] * n,
        "accepted": [4] * n, "reconnects": [0] * n}],
        record.Events([_plan()], 0, 3))


def test_percentiles_latency_and_lateness_on_hand_made_logs():
    assert metrics.percentile([1, 2, 3, 4, 5], 50) == 3
    assert metrics.percentile(range(101), 95) == 95
    ms = 1_000_000
    frames = _frames(due=[0, 10 * ms, 20 * ms], send=[1 * ms, 10 * ms, 26 * ms],
                     done=[3 * ms, 12 * ms, 30 * ms])
    assert metrics.lateness_ms(frames).tolist() == [1.0, 0.0, 6.0]
    assert metrics.post_ms(frames).tolist() == [2.0, 2.0, 4.0]
    # rows=4: frame f holds events 4f..4f+3; blocks name their newest event
    delivered = {"enter_ns": np.array([8, 19, 45, 99]) * ms,
                 "max_ts": np.array([4 * 3 + 3, 4 * 4 + 1, 4 * 5 + 3, 4 * 5])}
    got = metrics.block_latency_ms(frames, delivered, 4, 0, 50 * ms)
    assert got.tolist() == [8.0, 9.0, 25.0]  # from the DUE time; 99 is late
    got = metrics.block_latency_ms(frames, delivered, 4, 0, 50 * ms,
                                   since="done_ns")
    assert got.tolist() == [5.0, 7.0, 15.0]
    # a block whose newest event is a warm-up frame's gives no sample
    delivered = {"enter_ns": np.array([8]) * ms, "max_ts": np.array([2])}
    assert metrics.block_latency_ms(frames, delivered, 4, 0, 50 * ms).size == 0


def test_frame_numbering_round_trips():
    one = record.Events([_plan(producers=4, pool=8, rows=100)], 0, 3)
    for k in range(20):
        for p in range(4):
            f = loadgen.frame_number(k, p, warm=3, producers=4)
            assert one.source(f) == (0, p, k % 8)
    assert one.source(2) == (0, 4, 2)  # the parent's own
    specs = one.producer_specs()
    due = [loadgen.paced_due_ns(k, 0, 1000.0, sp["round_events"],
                                sp["lead_events"])
           for k in range(3) for sp in specs]
    assert due == sorted(due) and due[1] - due[0] == 100_000_000


def test_two_streams_share_one_numbering():
    """The producers of all input streams are numbered in one list; frames
    may differ in rows from stream to stream, event indexes do not
    collide, and the round's events come at the cell's total rate."""
    two = record.Events([_plan("A", producers=2, pool=3, rows=100),
                         _plan("B", producers=1, pool=2, rows=50)], 0, 2)
    assert (two.producers, two.stride, two.round_events) == (3, 100, 250)
    assert two.warm_total == 4
    # warm-up frames stream by stream, from each stream's virtual producer
    assert [two.source(f) for f in range(4)] == \
        [(0, 2, 0), (0, 2, 1), (1, 1, 0), (1, 1, 1)]
    specs = two.producer_specs()
    assert [(s["stream"], s["producer"], s["index"], s["lead_events"])
            for s in specs] == [("A", 0, 0, 0), ("A", 1, 1, 100),
                                ("B", 0, 2, 200)]
    assert all(s["warm"] == 4 and s["producers"] == 3 for s in specs)
    for k in range(7):
        for sp in specs:
            f = loadgen.frame_number(k, sp["index"], 4, 3)
            s, p, slot = two.source(f)
            assert (two.plans[s]["stream"], p) == (sp["stream"],
                                                   sp["producer"])
            assert slot == k % sp["pool"]
            assert two.frame_columns(f)["price"].size == two.plans[s]["rows"]
    due = [loadgen.paced_due_ns(k, 0, 1000.0, sp["round_events"],
                                sp["lead_events"])
           for k in range(2) for sp in specs]
    assert [d // 1_000_000 for d in due] == [0, 100, 200, 250, 350, 450]
    logs = [{"frame": [4 + i], "due_ns": [0], "send_ns": [0], "done_ns": [1],
             "status": [200], "accepted": [sp["params"]["rows_per_frame"]],
             "reconnects": [0]} for i, sp in enumerate(specs)]
    merged = record.merge_frame_logs(logs, two)
    assert merged["stream"].tolist() == [0, 0, 1]
    assert merged["rows"].tolist() == [100, 100, 50]


def test_a_connect_that_stands_is_given_up_and_tried_again(monkeypatch):
    """A listener whose accept queue is full drops the handshake, and the
    kernel would retry it for a minute; the producer gives up after
    CONNECT_PATIENCE_S and tries again on a new socket."""
    import socket
    monkeypatch.setattr(loadgen, "CONNECT_PATIENCE_S", 0.2)
    server = socket.socket()
    held = []
    try:
        server.bind(("127.0.0.1", 0))
        server.listen(0)
        port = server.getsockname()[1]
        for _ in range(3):  # fill the accept queue
            c = socket.socket()
            c.setblocking(False)
            c.connect_ex(("127.0.0.1", port))
            held.append(c)
        with pytest.raises(TimeoutError):
            loadgen.connect("127.0.0.1", port, timeout=0.5)
        while True:  # the queue empties: the next connect goes through
            server.settimeout(0.3)
            try:
                held.append(server.accept()[0])
            except TimeoutError:
                break
        conn, given_up = loadgen.connect("127.0.0.1", port, timeout=5.0)
        conn.close()
        assert given_up <= 5
    finally:
        for c in held:
            c.close()
        server.close()


# ------------------------------------------------------------ the generator


def test_generator_is_a_function_of_the_seed_alone():
    gen = registry.load_module("generators", "trades")
    a = gen.columns(PARAMS, 5, "TradeStream", 1, 2)
    b = gen.columns(PARAMS, 5, "TradeStream", 1, 2)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    for other in ((6, "TradeStream", 1, 2), (5, "Other", 1, 2),
                  (5, "TradeStream", 0, 2), (5, "TradeStream", 1, 3)):
        c = gen.columns(PARAMS, *other)
        assert not np.array_equal(a["symbol"], c["symbol"])
    codes = ["s", "f", "l"]
    assert bytes(sxf1.encode_frame(gen.wire_columns(a, codes), 512)) == \
        bytes(sxf1.encode_frame(gen.wire_columns(b, codes), 512))
    assert gen.symbol_strings([0, 42, 999999]) == \
        ["S0000000", "S0000042", "S0999999"]
    assert bytes(gen.symbol_bytes(np.array([42]))[0]) == b"S0000042"
    named = {**PARAMS, "symbols": ["WSO2", "IBM"]}
    ids = gen.columns(named, 5, "cseEventStream", 0, 0)["symbol"]
    assert set(ids.tolist()) == {0, 1}
    assert gen.symbol_strings([1, 0, 1], named) == ["IBM", "WSO2", "IBM"]


@pytest.mark.parametrize("extra", [
    {},  # groupby_1m's row: S%07d symbols, three attributes
    # filter_700's: upstream's two symbols and a creation stamp
    {"symbols": ["WSO2", "IBM"],
     "attributes": ["symbol", "price", "volume", "timestamp"],
     "event_index_attributes": ["timestamp"]}])
def test_patched_frame_decodes_equal_with_the_programs_decoder(extra):
    from siddhi_tpu.io import wire
    gen = registry.load_module("generators", "trades")
    params = {**PARAMS, **extra}
    cols = gen.columns(params, 9, "TradeStream", 0, 0)
    plan = [("symbol", np.dtype(np.int32), "s"),
            ("price", np.dtype(np.float32), "f"),
            ("volume", np.dtype(np.int64), "l")]
    if extra:
        plan.append(("timestamp", np.dtype(np.int64), "l"))
    body = sxf1.encode_frame(
        gen.wire_columns(cols, [c for *_, c in plan], params), 512)
    decoded = []
    for first in (0, 7 * 512):
        sxf1.patch_timestamps(body, first)
        (payload,) = list(wire.iter_frames(bytes(body)))
        ts, got, n = wire.decode_frame(payload, plan)
        assert n == 512 and np.array_equal(ts, np.arange(first, first + 512))
        if extra:  # the creation stamp is patched with the timestamps
            assert np.array_equal(got["timestamp"], ts)
        decoded.append(got)
        strings = wire.materialize_strings(got["symbol"])
        assert strings.tolist() == gen.symbol_strings(cols["symbol"], params)
        assert np.array_equal(got["price"],
                              cols["price"].astype(np.float32))
        assert np.array_equal(got["volume"], cols["volume"])
    # the program's own encoder, given the same rows, decodes to the same
    mine = wire.encode_frames(
        plan, {"symbol": np.array(gen.symbol_strings(cols["symbol"], params),
                                  dtype=object),
               "price": cols["price"], "volume": cols["volume"],
               "timestamp": np.arange(7 * 512, 8 * 512)}, 512,
        ts=np.arange(7 * 512, 8 * 512))
    (payload,) = list(wire.iter_frames(mine))
    ts, theirs, _ = wire.decode_frame(payload, plan)
    assert np.array_equal(ts, np.arange(7 * 512, 8 * 512))
    assert wire.materialize_strings(theirs["symbol"]).tolist() == \
        wire.materialize_strings(decoded[1]["symbol"]).tolist()


# ------------------------------------------------- what makes a run incorrect


class _Block:
    def __init__(self, ts):
        self.timestamps = np.asarray(ts, np.int64)
        self.count = self.timestamps.size
        self.is_expired = np.zeros(self.count, bool)


def _conserve(blocks, statuses=(200, 200), rows_in=None):
    """Two producers' frames 1 and 2 after one warm-up frame 0, 8 rows a
    frame, through filter_700's reference."""
    reference = registry.load_module("references", "filter_700")
    events = record.Events([_plan(producers=2, rows=8)], 3, 1)
    frames = record.merge_frame_logs([
        {"frame": [f], "due_ns": [0], "send_ns": [0], "done_ns": [1],
         "status": [status], "accepted": [8 * (status == 200)],
         "reconnects": [0]} for f, status in zip((1, 2), statuses)], events)
    sent = [0] + [f for f, s in zip((1, 2), statuses) if s == 200]
    keep = {f: f * 8 + np.nonzero(reference.passes(
        events.frame_columns(f), {}, "TradeStream"))[0] for f in (0, 1, 2)}
    stats = {"ingress_pipeline": {"TradeStream": {
        "rows_in": 8 * len(sent) if rows_in is None else rows_in}},
        "ingress_dropped": {}}
    run = {"frames": frames, "events": events, "config": {},
           "delivered": {"blocks": [_Block(b(keep)) for b in blocks]},
           "sent_extra": {0: 8}, "stats_end": stats}
    assert reference.expected_output_rows(run, sent) == sum(
        keep[f].size for f in sent)
    return reference.account(run), keep


def test_conservation_passes_a_clean_run_and_counts_events():
    out, keep = _conserve([lambda k: k[0], lambda k: k[1], lambda k: k[2]])
    assert out["conserved"] and out["failed"] == 0
    assert out["attempted"] == 16
    assert out["detail"]["rows_out"] == sum(v.size for v in keep.values())


@pytest.mark.parametrize("blocks, broken", [
    # a lost frame: frame 2's rows never come
    ([lambda k: k[0], lambda k: k[1]], "rows_out_is_full_windows"),
    # a duplicated row
    ([lambda k: k[0], lambda k: k[1], lambda k: k[2],
      lambda k: k[2][-1:]], "no_duplicates"),
    # a row for an event the filter drops
    ([lambda k: k[0], lambda k: np.setdiff1d(np.arange(8, 16), k[1])[:1],
      lambda k: k[1], lambda k: k[2]], "only_sent_events_that_passed"),
    # a producer's rows out of order
    ([lambda k: k[0], lambda k: k[1][::-1], lambda k: k[2]],
     "producer_order_kept"),
])
def test_injected_faults_turn_the_run_incorrect(blocks, broken):
    out, _ = _conserve(blocks)
    assert not out["conserved"] and not out["checks"][broken]
    if broken != "producer_order_kept":
        assert out["failed"] > 0


def test_a_refused_frame_counts_as_attempted_and_failed():
    out, _ = _conserve([lambda k: k[0], lambda k: k[1]], statuses=(200, 503))
    assert out["conserved"]  # nothing accepted went missing
    assert out["attempted"] == 16 and out["failed"] == 8


def test_engine_error_log_turns_correct_false(monkeypatch, capsys):
    """An ERROR the engine logged and carried on from makes the run
    incorrect, through the whole command."""
    import logging

    import deployment
    import run as bench_run

    orig = deployment.Deployment.warm

    def noisy(self, extra):
        logging.getLogger("siddhi_tpu").error("async readback failed")
        return orig(self, extra)

    monkeypatch.setattr(deployment.Deployment, "warm", noisy)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench_run.main(["--workload", "filter_700.saturate", "--seed", "1",
                         "--seconds", "1", "--trace", "0", "--rehearse"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and last["correct"] is False and last["failed"] == 0


def test_reference_catches_a_wrong_value():
    reference = registry.load_module("references", "groupby_1m")
    total, avg, count = reference.window_rows(
        np.array([1, 2, 1, 1]), np.array([1.0, 2.5, 0.25, 4.0]))
    assert total.tolist() == [1.0, 2.5, 1.25, 5.25]
    assert count.tolist() == [1, 1, 2, 3]
    assert avg.tolist() == [1.0, 2.5, 0.625, 1.75]
    assert reference.expected_rows(250, {"sizes": {"window": 100}}) == 200


# ------------------------------------------------------ peaks and rooflines


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert "cloud.google.com" in v5e["source"]
    least = roofline.least_seconds(roofline.agg_step(131072), "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(80 * 131072 / 819e9)


# ------------------------------------------------------- driven by data


def test_a_new_cell_config_and_metric_need_files_and_entries_only(tmp_path):
    """benchmarks/README.md's recipe with throw-away files: a third
    configuration with TWO input streams, its reference, a traffic mix, a
    fifth cell, a new end-to-end and a new per-layer metric, added to a
    copy of the benchmark by writing files and manifest entries: no file
    that was there is edited."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.
                    ignore_patterns("out", "__pycache__", ".pytest_cache"))
    os.symlink(os.path.join(REPO, "siddhi_tpu"), root / "siddhi_tpu")
    os.symlink(os.path.join(REPO, "native"), root / "native")
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    b = root / "benchmarks"
    config = json.load(open(b / "configs" / "filter_700.json"))
    config.update(name="two_desks", app_name="TwoDesks",
                  reference="two_desks")
    # two input streams of different rows, filtered into one output
    name, asynchronous, define, *query = config["app"]
    config["app"] = [
        name,
        asynchronous, define.replace("cseEventStream", "deskA"),
        asynchronous, define.replace("cseEventStream", "deskB"),
        *[ln.replace("cseEventStream", "deskA").replace("filt", "a")
          .replace("700 > price", "900 > price") for ln in query],
        *[ln.replace("cseEventStream", "deskB").replace("filt", "b")
          .replace("700 > price", "900 > price") for ln in query]]
    data = config["inputs"].pop("cseEventStream")
    config["inputs"] = {"deskA": data, "deskB": json.loads(json.dumps(data))}
    (b / "configs" / "two_desks.json").write_text(json.dumps(config))
    (b / "references" / "two_desks.py").write_text(
        (b / "references" / "filter_700.py").read_text()
        .replace("PRICE_CUT = 700.0", "PRICE_CUT = 900.0"))
    traffic = json.load(open(b / "traffic" / "saturate.json"))
    traffic.update(name="two_to_one", producers=2,
                   streams={"deskB": {"producers": 1,
                                      "rehearse_rows_per_frame": 128}})
    (b / "traffic" / "two_to_one.json").write_text(json.dumps(traffic))
    (b / "workloads" / "two_desks.two_to_one.json").write_text(
        json.dumps({"config": "two_desks", "traffic": "two_to_one"}))
    (b / "layer_metrics" / "front.frames_sent.py").write_text(
        "def read(run):\n    return float(run['frames']['frame'].size)\n")
    (b / "end_to_end" / "blocks_per_s.py").write_text(
        "def read(run):\n    return len(run['delivered']['blocks'])"
        " / run['seconds']\n")
    man = json.loads(json.dumps(MANIFEST))
    cell = "two_desks.two_to_one"
    man["configs"].append({
        "name": "two_desks", "source": "throw-away", "reduced": [],
        "file": "benchmarks/configs/two_desks.json", "why": "test"})
    man["workloads"].append({
        "name": cell, "config": "two_desks", "traffic": "two_to_one",
        "chips": 1, "why": "test"})
    for m in man["end_to_end"]:
        if m["name"] in ("events_per_s", "setup_s") and "workloads" in m:
            m["workloads"].append(cell)
    man["end_to_end"].append({
        "name": "blocks_per_s", "unit": "blocks/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": [cell]})
    man["per_layer"].append({
        "name": "front.frames_sent", "unit": "frames", "better": "higher",
        "source": "host_clock", "layer": "service front",
        "moves": "events_per_s", "workloads": [cell]})
    for m in man["per_layer"]:
        if m["name"] == "ingress.h2d_ms":
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    proc, lines = rehearse(cell, "--trace", "0", cwd=str(root), seconds="3")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["correct"] and last["failed"] == 0
    assert set(last["metrics"]) == {"events_per_s", "setup_s",
                                    "blocks_per_s"}
    assert last["metrics"]["blocks_per_s"]["value"] > 0
    detail = json.loads(lines[-2])
    account = detail["account"]
    # both streams fed, each through its own pipeline; 900 > price passes
    # more than 700 > price would: the new reference ran
    assert len(account["rows_in"]) == 2 and min(account["rows_in"]) > 0
    assert sum(account["rows_in"]) == account["sent_rows"]
    assert account["passed"] > 0.85 * account["sent_rows"]
    assert account["rows_out"] == account["passed"]

    proc, lines = rehearse(cell, "--trace", "1", cwd=str(root), seconds="3")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["correct"] and last["failed"] == 0
    assert last["metrics"]["front.frames_sent"]["value"] > 0
    assert last["metrics"]["ingress.h2d_ms"]["value"] > 0  # over both
    after = {p: p.read_bytes() for p in before}
    assert after == before
