"""`join_100k`: its reference's account on logs with a known fault, the two
copies of the per-event reference held to each other, the roofline's bytes
and the readers of the join's counters. Not tier-1 (`JAX_PLATFORMS=cpu
python -m pytest benchmarks/tests -q`); the cell's rehearsal end to end is
`test_harness.py`'s, which runs every cell of the manifest."""

import json
import os
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
import roofline_join  # noqa: E402
from tests.join_reference import WindowedJoin as PlainCopy  # noqa: E402

reference = registry.load_module("references", "join_100k")
CONFIG = registry.load_json("configs", "join_100k")
ROWS, WINDOW, KEYS = 64, 50, 40


class _Block:
    """What the reference reads of the program's ColumnarBlock; `symbol`
    holds the generator's ids in place of interned codes."""

    def __init__(self, timestamps, columns, is_expired=None, count=None,
                 codec=None):
        self.timestamps = np.asarray(timestamps, np.int64)
        self.columns = {k: np.asarray(v) for k, v in columns.items()}
        self.count = self.timestamps.size
        self.is_expired = np.zeros(self.count, bool)
        self._codec = codec

    def column(self, name):
        return self.columns[name]

    def strings(self, name):
        return [f"S{i:07d}" for i in self.columns[name].tolist()]


def _events(seed=5):
    traffic = {"producers": 2, "pool": 3, "rows_per_frame": ROWS,
               "rehearse_rows_per_frame": ROWS, "params": {"keys": KEYS}}
    plans = registry.stream_plans(CONFIG, traffic, rehearse=True)
    return record.Events(plans, seed, warm=2)


def _pairs_of(events, order, join):
    """frame -> (trade stamps, quote stamps) as `join` (a per-event
    reference) gives them over the serialisation `order`."""
    out = {}
    for f in order:
        side = events.source(f)[0]
        keys = events.frame_columns(f)["symbol"].tolist()
        pairs = join.frame(side, keys, range(f * ROWS, (f + 1) * ROWS))
        out[f] = (np.array([p[0] for p in pairs], np.int64),
                  np.array([p[1] for p in pairs], np.int64))
    return out


def _run(doctor=None, drops=None, order=None, statuses=None):
    """Four warm-up frames (two a stream), then three rounds of four
    producers (two a stream), run in `order`; one block a frame from the
    tests' copy of the per-event reference, `doctor`ed before the account
    sees it."""
    events = _events()
    measured = list(range(4, 16))
    order = order or [0, 1, 2, 3] + measured
    pairs = _pairs_of(events, order, PlainCopy(WINDOW))
    blocks = []
    for f in order:
        trade, quote = pairs[f]
        if not trade.size:
            continue
        side = events.source(f)[0]
        blocks.append([(trade, quote)[side], {
            "symbol": events.lookup(trade, ("symbol",))["symbol"],
            "tradePrice": events.lookup(trade, ("price",))["price"]
            .astype(np.float32),
            "quotePrice": events.lookup(quote, ("price",))["price"]
            .astype(np.float32),
            "tradeStamp": trade, "quoteStamp": quote}])
    if doctor:
        blocks = doctor(blocks) or blocks
    statuses = statuses or {}
    frames = record.merge_frame_logs([
        {"frame": [f for f in measured if (f - 4) % 4 == p],
         "due_ns": [0] * 3, "send_ns": [0] * 3, "done_ns": [1] * 3,
         "status": [statuses.get(f, 200) for f in measured
                    if (f - 4) % 4 == p],
         "accepted": [ROWS * (statuses.get(f, 200) == 200)
                      for f in measured if (f - 4) % 4 == p],
         "reconnects": [0] * 3} for p in range(4)], events)
    sent = [f for f in order if statuses.get(f, 200) == 200]
    by_stream = [sum(ROWS for f in sent if events.source(f)[0] == s)
                 for s in (0, 1)]
    delivered = {"blocks": [_Block(ts, cols) for ts, cols in blocks]}
    delivered["rows"] = np.array([b.count for b in delivered["blocks"]])
    delivered["enter_ns"] = np.arange(len(blocks), dtype=np.int64) * 10
    return {
        "frames": frames, "events": events, "delivered": delivered,
        "config": {**CONFIG, "sizes": {"window": WINDOW, "batch": ROWS}},
        "sent_extra": {f: ROWS for f in range(4)},
        "stats_end": {
            "ingress_pipeline": {
                plan["stream"]: {"rows_in": by_stream[s]}
                for s, plan in enumerate(events.plans)},
            "ingress_dropped": {}, "overflow": drops or {}}}


def test_account_passes_a_clean_log_and_counts_events():
    run = _run()
    out = reference.account(run)
    assert out["conserved"], out["failures"]
    assert out["failed"] == 0 and out["attempted"] == 12 * ROWS
    assert out["detail"]["rows_out"] == out["detail"]["pairs_expected"] > ROWS
    assert out["detail"]["frames"] == 16
    # frames 0 and 1 are the first stream's: they met an empty window
    assert out["detail"]["blocks"] == 14
    assert reference.completed(run, 0, 10 ** 9) == 12 * ROWS
    # the first measured frame's block is the third to arrive
    assert reference.completed(run, 0, 30) == ROWS
    assert reference.expected_output_rows(run, list(range(16))) == 14


def test_account_follows_the_serialisation_that_happened():
    """The same frames run in another order give other pairs; the account
    holds the log to ITS order, not to the frames' numbers."""
    order = [0, 1, 2, 3, 6, 4, 5, 7, 10, 11, 8, 9, 12, 13, 14, 15]
    assert reference.account(_run(order=order))["conserved"]
    straight = _run()
    shuffled = _run(order=order)
    assert [b.count for b in straight["delivered"]["blocks"]] != \
        [b.count for b in shuffled["delivered"]["blocks"]]


def _rows(block: int, pick):
    """A doctor that keeps rows `pick(n)` of one block, in that order."""
    def doctor(blocks):
        ts, cols = blocks[block]
        rows = np.asarray(pick(ts.size))
        blocks[block] = [ts[rows], {k: v[rows] for k, v in cols.items()}]
    return doctor


def _expired_build_row(blocks):
    # block 5 answers a frame of the first stream (trades probe quotes):
    # point a pair's quote at a row the window evicted long ago: no live row
    quote = blocks[5][1]["quoteStamp"] = blocks[5][1]["quoteStamp"].copy()
    quote[0] = 2 * ROWS  # frame 2's first row: two frames of quotes ago


FAULTS = {
    "a_pair_removed": (_rows(5, lambda n: np.arange(1, n)),
                       {"rows_out_equal_the_reference_count",
                        "no_pair_missing"}),
    "a_pair_doubled": (_rows(5, lambda n: np.r_[0, np.arange(n)]),
                       {"rows_out_equal_the_reference_count",
                        "no_pair_twice",
                        "by_probe_then_oldest_match_first"}),
    "two_rows_swapped": (_rows(5, lambda n: np.r_[1, 0, np.arange(2, n)]),
                         {"by_probe_then_oldest_match_first"}),
    "a_build_row_that_had_expired": (_expired_build_row,
                                     {"only_true_pairs_of_live_rows",
                                      "no_pair_missing"}),
    # the frame is then placed where its producer's next answered frame
    # stands, so later windows differ too: more checks fail than these
    "a_block_lost": (lambda blocks: blocks[:7] + blocks[8:],
                     {"every_frame_answered",
                      "rows_out_equal_the_reference_count",
                      "no_pair_missing"}),
    "a_block_twice": (lambda blocks: blocks + blocks[-1:],
                      {"log_is_a_serialisation"}),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_account_fails_a_log_with_a_fault(fault):
    doctor, broken = FAULTS[fault]
    out = reference.account(_run(doctor))
    failed = {k for k, ok in out["checks"].items() if not ok}
    assert failed >= broken if fault == "a_block_lost" else failed == broken
    assert out["failures"] and not out["conserved"]
    if fault != "a_block_twice":
        assert out["failed"] > 0


def test_account_fails_a_non_zero_drop_counter():
    out = reference.account(_run(
        drops={"query:join.join_pairs_dropped": 3}))
    assert not out["checks"]["join_pairs_dropped_zero"]
    assert any("dropped pairs" in f for f in out["failures"])
    # another query's overflow is not the join's to answer for
    assert reference.account(_run(
        drops={"query:other.window_ring_overflow": 1}))["conserved"]


def test_a_refused_frame_counts_as_attempted_and_failed():
    order = [f for f in range(16) if f != 9]
    out = reference.account(_run(order=order, statuses={9: 503}))
    assert out["conserved"], out["failures"]
    assert out["attempted"] == 12 * ROWS and out["failed"] == ROWS


def test_the_two_copies_of_the_reference_agree_on_seeded_frames():
    events = _events(seed=11)
    order = [0, 2, 1, 3, 5, 4, 7, 6, 8, 9, 11, 10]
    mine = _pairs_of(events, order, reference.WindowedJoin(WINDOW))
    theirs = _pairs_of(events, order, PlainCopy(WINDOW))
    assert sum(t.size for t, _ in mine.values()) > 5 * ROWS
    for f in order:
        assert np.array_equal(mine[f][0], theirs[f][0])
        assert np.array_equal(mine[f][1], theirs[f][1])


def test_sample_compares_rows_to_the_per_event_loop():
    """Every block (14 < 64) against the per-event loop: all five columns
    and the timestamp; a block short of a row, and a price off by one bit."""
    clean = reference.verify_sample(_run(), np.random.default_rng(0))
    assert clean == {"failures": [], "sampled": 14, "unit": "blocks"}
    out = reference.verify_sample(_run(_rows(5, lambda n: np.arange(1, n))),
                                  np.random.default_rng(0))
    assert len(out["failures"]) == 1 and "rows, the per-event reference" \
        in out["failures"][0]

    def one_bit(blocks):
        price = blocks[3][1]["quotePrice"] = blocks[3][1]["quotePrice"].copy()
        price[-1] = np.nextafter(price[-1], np.float32(0))

    out = reference.verify_sample(_run(one_bit), np.random.default_rng(0))
    assert len(out["failures"]) == 1 and "'quotePrice'" in out["failures"][0]


def test_join_roofline_bytes_at_the_deployments_shapes():
    sizes = CONFIG["sizes"]
    keys = CONFIG["inputs"]["cseEventStream"]["params"]["keys"]
    assert (sizes["batch"], sizes["window"], keys) == (131072, 100000,
                                                        100000)
    work = roofline_join.join_step(sizes["batch"], sizes["window"], keys)
    # 34 in + 32 packed + 16 multimap + 4 head + (12 + 32 + 38) a pair
    assert work["bytes"] == 168 * 131072 == 22_020_096
    least = roofline.least_seconds(work, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(22_020_096 / 819e9)
    # half the keys: two matches a probe
    assert roofline_join.join_step(131072, 100000, 50000)["bytes"] \
        == (86 + 2 * 82) * 131072


def test_join_readers_read_the_programs_counters_and_nothing_from_a_parent():
    fill = registry.load_module("layer_metrics", "join.block_fill_pct")
    dropped = registry.load_module("layer_metrics", "join.pairs_dropped")
    step = registry.load_module("layer_metrics", "join.step_ms")
    share = registry.load_module("layer_metrics", "join_step_roofline")

    def joins(steps, dropped_pairs=0):
        return {"joins": {"join": {
            "steps": {"left": steps, "right": steps},
            "out_lanes": 2 * steps * 524288, "pairs_dropped": dropped_pairs}}}

    run = {"stats0": joins(3), "stats1": joins(13, 2), "t0_ns": 0,
           "t_end_ns": 100, "device": {"platform": "tpu",
                                       "kind": "TPU v5 lite"},
           "config": CONFIG,
           "delivered": {"enter_ns": np.array([-5, 10, 20, 200]),
                         "rows": np.array([1, 131072, 131072, 7])},
           "reduced_trace": {"module_seconds": {
               "jit_join_probe_left(123)": [1.0, 10],
               "jit_join_probe_right(456)": [2.0, 10],
               "jit_step(789)": [50.0, 10],
               "jit__wire_pack(1)": [0.5, 20]}}}
    assert fill.read(run) == pytest.approx(25.0)
    assert dropped.read(run) == 2.0
    assert step.read(run) == pytest.approx(150.0)
    assert share.read(run) == pytest.approx(
        100 * (22_020_096 / 819e9) / 0.15)
    parent = {**run, "stats0": {}, "stats1": {}, "reduced_trace": {
        "module_seconds": {"jit_step(1)": [1.0, 10]}}}
    for reader in (fill, dropped, step, share):
        assert reader.read(parent) is None
    cpu = {**run, "device": {"platform": "cpu", "kind": "cpu"}}
    assert step.read(cpu) is None and share.read(cpu) is None


def test_manifest_lists_the_cell_wherever_saturate_cells_report():
    man = registry.manifest()
    cell = "join_100k.saturate"
    for m in man["end_to_end"] + man["per_layer"]:
        w = m.get("workloads")
        if w and {"groupby_1m.saturate", "filter_700.saturate"} <= set(w):
            # the four idle.feeder_* shares read the device's idle gaps,
            # and this cell's device has none (PERF.md §5): nothing to read
            assert (cell in w) != m["name"].startswith("idle.feeder_"), \
                m["name"]
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine == ["join.step_ms", "join_step_roofline",
                    "join.block_fill_pct", "join.pairs_dropped"]
    config = json.load(open(os.path.join(BENCH, "configs",
                                         "join_100k.json")))
    entry = next(c for c in man["configs"] if c["name"] == "join_100k")
    assert entry["source"] == config["source"] and entry["reduced"] == []
