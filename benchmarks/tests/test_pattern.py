"""`pattern_ab`: its reference's account on logs with a known fault, the
serialisation read back from a log with consecutive trade frames, the two
copies of the per-event reference held to each other, the roofline's bytes
and the readers of the pattern's counters. Not tier-1 (`JAX_PLATFORMS=cpu
python -m pytest benchmarks/tests -q`); the rehearsal of `pattern_ab.saturate`
end to end is `test_harness.py`'s, which runs every cell of the manifest."""

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
import roofline_pattern  # noqa: E402
from tests.pattern_reference import EveryAThenB as PlainCopy  # noqa: E402

reference = registry.load_module("references", "pattern_ab")
CONFIG = registry.load_json("configs", "pattern_ab")
ROWS, KEYS = 64, 40
A, B = 0, 1
# two warm-up frames a stream, then three rounds of four producers (two a
# stream): frames 4, 5, 8, 9, 12, 13 are trades, 6, 7, 10, 11, 14, 15 quotes
STRAIGHT = list(range(16))


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


def _pairs_of(events, order, pattern):
    """quote frame -> (trade stamps, quote stamps) as `pattern` (a
    per-event reference) gives them over the serialisation `order`."""
    out = {}
    for f in order:
        side = events.source(f)[0]
        stamps = list(range(f * ROWS, (f + 1) * ROWS))
        pairs = pattern.frame(side, events.frame_columns(f)["symbol"]
                              .tolist(), stamps, stamps)
        if side == B:
            out[f] = (np.array([p[0] for p in pairs], np.int64),
                      np.array([p[1] for p in pairs], np.int64))
    return out


def _run(doctor=None, drops=None, order=None, statuses=None, within=None):
    """The frames run in `order`; one block a quote frame from the tests'
    copy of the per-event reference, `doctor`ed before the account sees
    it."""
    events = _events()
    measured = list(range(4, 16))
    order = order or STRAIGHT
    within = within or CONFIG["within_ticks"]
    pairs = _pairs_of(events, order, PlainCopy(within))
    blocks = []
    for f in order:
        trade, quote = pairs.get(f, (np.zeros(0), None))
        if not trade.size:
            continue
        blocks.append([quote, {
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
        "config": {**CONFIG, "within_ticks": within,
                   "sizes": {"pending": 2048, "batch": ROWS}},
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
    assert out["detail"]["rows_out"] == out["detail"]["rows_expected"] > ROWS
    assert out["detail"]["frames"] == 16 and out["detail"]["blocks"] == 8
    assert out["detail"]["waiting_high_water"] >= 2 * ROWS
    # every measured quote frame's events, and every measured trade whose
    # row came out (the warm-up's trades are not the producers')
    stride = run["events"].stride
    rows_of_measured_trades = sum(
        int((b.column("tradeStamp") // stride >= 4).sum())
        for b in run["delivered"]["blocks"])
    assert reference.completed(run, 0, 10 ** 9) \
        == 6 * ROWS + rows_of_measured_trades
    # the two warm-up quote frames' blocks hold warm-up trades alone
    assert reference.completed(run, 0, 20) == 0
    assert reference.expected_output_rows(run, STRAIGHT) == 8 - 2


@pytest.mark.parametrize("order", [
    # consecutive trade frames, of one producer and of two, before a quote
    [0, 1, 2, 3, 4, 5, 8, 6, 9, 7, 10, 12, 13, 11, 14, 15],
    [0, 1, 2, 3, 5, 4, 9, 8, 13, 6, 7, 10, 12, 11, 14, 15],
    # quote frames in a row, and trades that meet no quote before the end
    [0, 1, 2, 3, 4, 6, 7, 5, 10, 11, 14, 8, 9, 15, 12, 13],
], ids=["runs_of_trades", "all_trades_of_a_stream_first", "quotes_in_a_row"])
def test_the_serialisation_is_read_back_from_the_rows(order):
    """Trade frames yield no block: their place is read from the rows (the
    first block that names them; among those of one gap, the order in which
    a quote lists its matches). The account then holds the log to THAT
    order, not to the frames' numbers."""
    run = _run(order=order)
    ser = reference.serialisation(run)
    assert not ser["failures"]
    # frames whose rows were all delivered stand exactly where they ran;
    # two trade frames with no symbol in common that a later quote took may
    # stand either way, and a trade frame no row names stands last: the
    # replay is the judge
    assert [f for f in ser["order"] if ser["side"][f] == B] \
        == [f for f in order if f in (2, 3, 6, 7, 10, 11, 14, 15)]
    if order[-1] not in (12, 13):
        assert ser["order"] == order
    out = reference.account(run)
    assert out["conserved"], out["failures"]
    straight = _run()
    assert [b.count for b in straight["delivered"]["blocks"]] != \
        [b.count for b in run["delivered"]["blocks"]]


def _rows(block: int, pick):
    """A doctor that keeps rows `pick(n)` of one block, in that order."""
    def doctor(blocks):
        ts, cols = blocks[block]
        rows = np.asarray(pick(ts.size))
        blocks[block] = [ts[rows], {k: v[rows] for k, v in cols.items()}]
    return doctor


def _wrong_trade(blocks):
    # a row that names a trade of another symbol, still waiting
    trade = blocks[4][1]["tradeStamp"] = blocks[4][1]["tradeStamp"].copy()
    trade[0] += 1


def _a_later_quote(blocks):
    # the trade's row moved to the NEXT quote frame of its symbol's... any
    # later block: its first quote was skipped
    ts, cols = blocks[4]
    moved = {k: v[:1] for k, v in cols.items()}
    blocks[4] = [ts[1:], {k: v[1:] for k, v in cols.items()}]
    ts5, cols5 = blocks[5]
    moved["quoteStamp"] = ts5[:1]
    blocks[5] = [np.r_[ts5[:1], ts5],
                 {k: np.r_[moved[k], v] for k, v in cols5.items()}]


ROWS_DIFFER = {"rows_out_equal_the_reference_count",
               "every_block_is_the_replays_rows_in_order"}
FAULTS = {
    "a_row_dropped": (_rows(4, lambda n: np.arange(1, n)), ROWS_DIFFER),
    "a_row_doubled": (_rows(4, lambda n: np.r_[0, np.arange(n)]),
                      ROWS_DIFFER),
    "two_rows_swapped": (_rows(4, lambda n: np.r_[1, 0, np.arange(2, n)]),
                         {"every_block_is_the_replays_rows_in_order"}),
    "a_wrong_row": (_wrong_trade,
                    {"every_block_is_the_replays_rows_in_order"}),
    "a_quote_skipped_for_a_later_one": (
        _a_later_quote, {"every_block_is_the_replays_rows_in_order"}),
    "a_block_lost": (lambda blocks: blocks[:5] + blocks[6:],
                     {"every_quote_frame_answered_once",
                      "rows_out_equal_the_reference_count"}),
    "a_block_twice": (lambda blocks: blocks + blocks[-1:],
                      {"log_is_a_serialisation",
                       "every_quote_frame_answered_once"}),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_account_fails_a_log_with_a_fault(fault):
    doctor, broken = FAULTS[fault]
    out = reference.account(_run(doctor))
    failed = {k for k, ok in out["checks"].items() if not ok}
    assert failed >= broken, failed
    assert out["failures"] and not out["conserved"]
    if fault != "a_block_twice":
        assert out["failed"] > 0


def test_a_run_that_dropped_partial_matches_ends_at_the_counter():
    """Checked first and alone: a parent whose table holds 1,024 loses most
    of what it is sent, and nothing is replayed against that."""
    run = _run(drops={"query:pattern.pattern_pending_dropped": 77927})
    out = reference.account(run)
    assert out["checks"] == {"pattern_pending_dropped_zero": False}
    assert not out["conserved"] and out["failed"] == out["attempted"]
    assert any("dropped partial matches" in f for f in out["failures"])
    assert "pattern_serialisation" not in run  # nothing was replayed
    assert reference.verify_sample(run, np.random.default_rng(0)) \
        == {"failures": [], "sampled": 0, "unit": "blocks"}
    assert reference.completed(run, 0, 10 ** 9) > 0  # and nothing raises
    # another query's overflow is not the pattern's to answer for
    assert reference.account(_run(
        drops={"query:other.window_ring_overflow": 1}))["conserved"]


def test_a_refused_frame_counts_as_attempted_and_failed():
    order = [f for f in STRAIGHT if f != 9]
    out = reference.account(_run(order=order, statuses={9: 503}))
    assert out["conserved"], out["failures"]
    assert out["attempted"] == 12 * ROWS and out["failed"] == ROWS


# producer 0's trade frames (4, 8, 12) run late, as those of a producer that
# was held up do: frame 4 right before quote frame 10, frame 8 before 14
LAGGING = [0, 1, 2, 3, 5, 6, 7, 9, 4, 10, 11, 8, 13, 14, 15, 12]


@pytest.mark.parametrize("order,within", [(STRAIGHT, 4 * ROWS),
                                          (LAGGING, 6 * ROWS)])
def test_the_bound_lets_trades_go_and_the_account_follows(order, within):
    """Per arriving quote, against its own stamp: a trade more than the
    bound older is let go, whatever its symbol. Under LAGGING the bound
    falls INSIDE frames 10 and 14 (a quote at lane j is within the bound of
    the late frame's trade at lane i only while j <= i), where a frame's
    newest stamp would let go of what its older quotes still match. Both
    copies and the vectorised replay agree."""
    loose, tight = _run(order=order), _run(order=order, within=within)
    assert sum(b.count for b in tight["delivered"]["blocks"]) \
        < sum(b.count for b in loose["delivered"]["blocks"])
    out = reference.account(tight)
    assert out["conserved"], out["failures"]
    assert out["detail"]["let_go"] > 0
    if order is LAGGING:
        late = [b.column("tradeStamp") // ROWS
                for b in tight["delivered"]["blocks"]]
        assert any((f == 4).any() for f in late) \
            and any((f == 8).any() for f in late)
    # held to the looser bound, the same log fails
    assert not reference.account(
        {**tight, "config": loose["config"]})["conserved"]


def test_the_two_copies_of_the_reference_agree_on_seeded_frames():
    events = _events(seed=11)
    order = [0, 2, 1, 3, 5, 4, 7, 6, 8, 9, 11, 10]
    for within in (None, 3 * ROWS):
        mine = _pairs_of(events, order, reference.EveryAThenB(within))
        theirs = _pairs_of(events, order, PlainCopy(within))
        assert sum(t.size for t, _ in mine.values()) > 2 * ROWS
        for f in mine:
            assert np.array_equal(mine[f][0], theirs[f][0])
            assert np.array_equal(mine[f][1], theirs[f][1])


def test_sample_compares_rows_to_the_per_event_loop():
    """Every block (8 < 64) against the per-event loop: all five columns and
    the timestamp; a block short of a row, and a price off by one bit."""
    clean = reference.verify_sample(_run(), np.random.default_rng(0))
    assert clean == {"failures": [], "sampled": 8, "unit": "blocks"}
    out = reference.verify_sample(_run(_rows(4, lambda n: np.arange(1, n))),
                                  np.random.default_rng(0))
    assert out["failures"] and "rows, the per-event reference" \
        in out["failures"][0]

    def one_bit(blocks):
        price = blocks[3][1]["tradePrice"] = blocks[3][1]["tradePrice"].copy()
        price[-1] = np.nextafter(price[-1], np.float32(0))

    out = reference.verify_sample(_run(one_bit), np.random.default_rng(0))
    assert len(out["failures"]) == 1 and "'tradePrice'" in out["failures"][0]


def test_pattern_roofline_bytes_at_the_deployments_shapes():
    sizes = CONFIG["sizes"]
    assert (sizes["batch"], sizes["pending"]) == (131072, 1 << 20)
    trade = roofline_pattern.trade_step(sizes["batch"])
    quote = roofline_pattern.quote_step(sizes["batch"])
    # 34 in + 50 entry + 4 free slot; 34 in + 4 probe + 50 + 1 + 38 a match
    assert trade["bytes"] == 88 * 131072
    assert quote["bytes"] == 127 * 131072
    for work in (trade, quote):
        least = roofline.least_seconds(work, "TPU v5 lite")
        assert least["bound"] == "memory"
        assert least["seconds"] == pytest.approx(work["bytes"] / 819e9)


def test_pattern_readers_read_the_programs_counters_and_nothing_from_a_parent():
    fill = registry.load_module("layer_metrics", "pattern.block_fill_pct")
    dropped = registry.load_module("layer_metrics", "pattern.pending_dropped")
    pending = registry.load_module("layer_metrics",
                                   "pattern.pending_fill_pct")
    step = registry.load_module("layer_metrics", "pattern.step_ms")
    share = registry.load_module("layer_metrics", "pattern_step_roofline")

    def patterns(steps, hwm=0, lost=0):
        return {"patterns": {"pattern": {
            "steps": {"cseEventStream": steps, "quoteEventStream": steps},
            "pending_capacity": 1 << 20, "live_hwm": hwm,
            "out_lanes": steps * ((1 << 20) + 1), "pending_dropped": lost}}}

    run = {"stats0": patterns(3, hwm=900000), "stats1": patterns(13, 262144,
                                                                 2),
           "trace": {"stats_open": patterns(7, 524288),
                     "stats_close": patterns(9, 300000)},
           "t0_ns": 0, "t_end_ns": 100,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "config": CONFIG,
           "delivered": {"enter_ns": np.array([-5, 10, 20, 200]),
                         "rows": np.array([1, 131072, 131072, 7])},
           "reduced_trace": {"module_seconds": {
               "jit_pattern_step_cseEventStream(123)": [0.5, 10],
               "jit_pattern_step_quoteEventStream(456)": [2.0, 10],
               "jit_pattern_heartbeat(7)": [9.0, 1],
               "jit_step(789)": [50.0, 10],
               "jit__wire_pack(1)": [0.5, 20]}}}
    assert fill.read(run) == pytest.approx(
        100 * 262144 / (10 * ((1 << 20) + 1)))
    assert dropped.read(run) == 2.0
    # the high water since the window opened: the slice's first report's
    assert pending.read(run) == pytest.approx(50.0)
    assert pending.read({**run, "trace": None}) == pytest.approx(25.0)
    assert step.read(run) == pytest.approx(125.0)
    assert share.read(run) == pytest.approx(
        100 * (10 * 88 * 131072 / 819e9 + 10 * 127 * 131072 / 819e9) / 2.5)
    parent = {**run, "stats0": {}, "stats1": {}, "trace": None,
              "reduced_trace": {"module_seconds": {"jit_step(1)": [1.0, 10]}}}
    for reader in (fill, dropped, pending, step, share):
        assert reader.read(parent) is None
    cpu = {**run, "device": {"platform": "cpu", "kind": "cpu"}}
    assert step.read(cpu) is None and share.read(cpu) is None


def test_manifest_lists_the_new_cell_wherever_saturate_cells_report():
    man = registry.manifest()
    for m in man["end_to_end"] + man["per_layer"]:
        w = set(m.get("workloads") or ())
        if {"groupby_1m.saturate", "filter_700.saturate"} <= w:
            # the four idle.feeder_* shares read the device's idle gaps
            assert ("pattern_ab.saturate" in w) \
                != m["name"].startswith("idle.feeder_"), m["name"]
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == ["pattern_ab.saturate"]]
    assert mine == ["pattern.step_ms", "pattern_step_roofline",
                    "pattern.pending_fill_pct", "pattern.pending_dropped",
                    "pattern.block_fill_pct"]
    config = json.load(open(os.path.join(BENCH, "configs",
                                         "pattern_ab.json")))
    entry = next(c for c in man["configs"] if c["name"] == "pattern_ab")
    assert entry["source"] == config["source"] and entry["reduced"] == []
    # join_100k.paced, owed since PR 26, was measured and left out (PERF.md
    # section 7): its p50 did not repeat within half the bound
    assert "join_100k.paced" not in [w["name"] for w in man["workloads"]]
