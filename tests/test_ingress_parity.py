"""Parallel-ingress parity & conservation tests (core/ingress.py).

The acceptance bar: the ingress pipeline must be INVISIBLE downstream — the
same single-producer row stream yields bit-identical delivered blocks
(timestamps, every column including string dictionary codes, expiry flags)
whether it runs through the lock-free pipeline or the plain synchronous
staging path, and with either the C colring or the pure-Python fallback
underneath. CI runs this module twice: once natively and once with
SIDDHI_NATIVE=0, so both ring implementations face the same oracle.
Multi-producer runs cannot promise delivery order, so their invariant is
exact conservation: sent == delivered + dropped.
"""

import threading
import time

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu import native as native_mod

pytestmark = pytest.mark.smoke

BS = 64  # micro-batch capacity for both variants (buffer.size == batch_size)

APP_PIPE = f"""
@app:name('Pipe')
@Async(buffer.size='{BS}', workers='2')
define stream TradeStream (symbol string, price double, volume long);
@info(name='q')
from TradeStream[price < 700.0]
select symbol, price, volume
insert into OutStream;
"""

#: same query, no @Async: the synchronous staging path is the oracle
APP_SERIAL = """
@app:name('Serial')
define stream TradeStream (symbol string, price double, volume long);
@info(name='q')
from TradeStream[price < 700.0]
select symbol, price, volume
insert into OutStream;
"""


def _rows(n: int, seed: int = 11):
    rng = np.random.default_rng(seed)
    ks = rng.integers(1, 40, n)
    ps = rng.uniform(1.0, 1000.0, n)
    vs = rng.integers(1, 1000, n)
    rows = [(f"S{int(k)}", float(p), int(v))
            for k, p, v in zip(ks, ps, vs)]
    for i in range(0, n, 17):  # sprinkle nulls through the string column
        rows[i] = (None,) + rows[i][1:]
    return rows


def _capture(app: str, feed, *, batch_size=None, out="OutStream",
             streams=("TradeStream",)):
    """Build, feed via `feed(handler, runtime)` (one handler per name in
    `streams`), return the blocks delivered to `out` as host tuples (ts,
    {col: array}, expired) for bit-exact comparison."""
    kw = {"batch_size": batch_size} if batch_size else {}
    rt = SiddhiManager().create_siddhi_app_runtime(app, **kw)
    blocks: list = []
    rt.add_callback(out, lambda b: blocks.append(
        (b.timestamps.copy(),
         {k: v.copy() for k, v in b.columns.items()},
         b.is_expired.copy())), columnar=True)
    rt.start()
    try:
        feed(*(rt.get_input_handler(s) for s in streams), rt)
        rt.drain()
    finally:
        rt.shutdown()
    return blocks


def _assert_blocks_identical(got, want):
    assert len(got) == len(want)
    for (gt, gc, ge), (wt, wc, we) in zip(got, want):
        np.testing.assert_array_equal(gt, wt)
        np.testing.assert_array_equal(ge, we)
        assert gc.keys() == wc.keys()
        for k in wc:
            assert gc[k].dtype == wc[k].dtype, k
            np.testing.assert_array_equal(gc[k], wc[k], err_msg=k)


def _pipeline_of(rt):
    return rt.junctions["TradeStream"]._pipeline


class TestBitParity:
    """Single producer: identical chunk boundaries, padding, and interning
    order are guaranteed by construction — so the blocks must match bit for
    bit, dictionary codes included."""

    def test_rows_path(self):
        rows = _rows(500)
        tss = np.arange(1, 501, dtype=np.int64)

        def feed(h, rt):
            h.send_batch(rows, timestamps=tss)
            rt.flush()

        pipe = _capture(APP_PIPE, feed)
        serial = _capture(APP_SERIAL, feed, batch_size=BS)
        assert sum(len(b[0]) for b in pipe) > 0
        _assert_blocks_identical(pipe, serial)

    def test_columns_path(self):
        rows = _rows(300, seed=12)
        cols = {
            "symbol": np.array([r[0] for r in rows], dtype=object),
            "price": np.array([r[1] for r in rows]),
            "volume": np.array([r[2] for r in rows], dtype=np.int64),
        }
        tss = np.arange(10, 310, dtype=np.int64)

        def feed(h, rt):
            h.send_columns(cols, timestamps=tss)
            rt.flush()

        pipe = _capture(APP_PIPE, feed)
        serial = _capture(APP_SERIAL, feed, batch_size=BS)
        _assert_blocks_identical(pipe, serial)

    def test_wire_frames_path(self):
        from siddhi_tpu.io import wire
        rows = _rows(400, seed=13)
        cols = {
            "symbol": np.array([r[0] for r in rows], dtype=object),
            "price": np.array([r[1] for r in rows]),
            "volume": np.array([r[2] for r in rows], dtype=np.int64),
        }
        tss = np.arange(5, 405, dtype=np.int64)

        def feed_frames(h, rt):
            plan = wire.schema_plan(h.junction.definition)
            body = wire.encode_frames(plan, cols, 400, ts=tss, chunk=96)
            assert wire.deliver_frames(h, body) == 400
            rt.flush()

        def feed_serial(h, rt):
            h.send_columns(cols, timestamps=tss)
            rt.flush()

        pipe = _capture(APP_PIPE, feed_frames)
        serial = _capture(APP_SERIAL, feed_serial, batch_size=BS)
        _assert_blocks_identical(pipe, serial)

    def test_pipeline_actually_engaged(self):
        """Guard against the parity tests silently comparing serial vs
        serial (e.g. the gate falling back): the @Async(workers=) app must
        run the pipeline, and its stats must show the traffic."""
        rows = _rows(200, seed=14)
        tss = np.arange(1, 201, dtype=np.int64)
        seen: dict = {}

        def feed(h, rt):
            p = _pipeline_of(rt)
            assert p is not None, "pipeline did not engage"
            h.send_batch(rows, timestamps=tss)
            rt.flush()
            seen.update(p.stats_snapshot())

        _capture(APP_PIPE, feed)
        assert seen["rows_in"] == 200
        assert seen["batches_delivered"] >= 1
        assert seen["ring_depth_hwm"] >= 1
        assert set(seen["stage_ms"]) == {
            "wire", "claim_wait", "decode", "ticket_wait",
            "intern_lock_wait", "intern", "fill", "h2d", "hold",
            "lock_wait", "dispatch", "device"}

    def test_fallback_ring_selected_without_native(self):
        """With SIDDHI_NATIVE=0 (or the C module missing) the pipeline must
        ride the pure-Python ring — same API, same parity oracle."""
        from siddhi_tpu.core.ingress import _PyColRing

        def feed(h, rt):
            p = _pipeline_of(rt)
            assert p is not None
            if native_mod.available() and hasattr(native_mod.native,
                                                 "colring_new"):
                assert not isinstance(p.ring, _PyColRing)
            else:
                assert isinstance(p.ring, _PyColRing)
            h.send_batch(_rows(64), timestamps=np.arange(64, dtype=np.int64))
            rt.flush()

        _capture(APP_PIPE, feed)


# ---------------------------------------------------- paced sending
#
# Full frames with a pause between them: the feeder meets an empty ring
# after every upload and delivers the batch it holds there, where it used
# to keep it until the next frame. Earlier, not different: the batches,
# their boundaries and their order are the synchronous path's, bit for bit.

PAUSE_S = 0.03

JOIN_STREAMS = ("cseEventStream", "quoteEventStream")
JOIN_BS, JOIN_WINDOW = 64, 200


def _join_app(asynchronous: bool) -> str:
    """tests/test_join_reference.py's app; without its `@Async` lines the
    same two streams stage synchronously: the oracle."""
    from .test_join_reference import APP
    text = APP.format(batch=JOIN_BS, window=JOIN_WINDOW)
    if not asynchronous:
        text = "\n".join(line for line in text.splitlines()
                         if not line.startswith("@Async"))
    return text


def _single_case():
    """(pipeline app, serial app, out, streams, frames): five frames of two
    chunks each into the filter."""
    frames = []
    for f in range(5):
        rows = _rows(2 * BS, seed=30 + f)
        frames.append((0, {
            "symbol": np.array([r[0] for r in rows], dtype=object),
            "price": np.array([r[1] for r in rows]),
            "volume": np.array([r[2] for r in rows], dtype=np.int64),
        }, np.arange(f * 1000, f * 1000 + 2 * BS, dtype=np.int64)))
    return APP_PIPE, APP_SERIAL, "OutStream", ("TradeStream",), BS, frames


def _join_case():
    """Ten one-chunk frames over the two streams of the join, sides in runs
    and alternating: a frame's pairs depend on every frame before it on the
    other side, so an order or a boundary that moved shows in the rows."""
    frames = []
    for f, side in enumerate((0, 1, 1, 0, 1, 0, 0, 0, 1, 1)):
        rng = np.random.default_rng([41, f])
        index = np.arange(f * 1000, f * 1000 + JOIN_BS, dtype=np.int64)
        frames.append((side, {
            "symbol": np.array([f"S{k:05d}" for k in rng.integers(
                0, JOIN_WINDOW, JOIN_BS).tolist()], dtype=object),
            "price": (rng.integers(1, 4000, JOIN_BS) * 0.25).astype(
                np.float32),
            "volume": np.ones(JOIN_BS, np.int64),
            "timestamp": index}, index))
    return (_join_app(True), _join_app(False), "joinedStream", JOIN_STREAMS,
            JOIN_BS, frames)


@pytest.mark.parametrize("case", [_single_case, _join_case],
                         ids=["single_stream", "two_stream_join"])
def test_paced_frames_match_the_synchronous_path(case):
    app_pipe, app_serial, out, streams, bs, frames = case()
    chunks = sum(len(ts) // bs for _, _, ts in frames)
    seen: dict = {}

    def feed_paced(*args):
        *handlers, rt = args
        pipes = [rt.junctions[s]._pipeline for s in streams]
        assert all(p is not None for p in pipes), "pipeline did not engage"
        sent = 0
        for side, cols, ts in frames:
            handlers[side].send_columns(cols, timestamps=ts)
            sent += len(ts) // bs
            # every chunk of the frame is delivered with nothing behind it
            # and no flush: on the parent the last one stood until the next
            # frame of its own stream
            # (the first wait includes the steps' compile: seconds on a
            # loaded machine; the deadline only tells a hold from a delivery)
            deadline = time.monotonic() + 30.0
            while sum(p._batches for p in pipes) < sent:
                assert time.monotonic() < deadline, \
                    [p.stats_snapshot() for p in pipes]
                time.sleep(0.001)
            time.sleep(PAUSE_S)
        for s, p in zip(streams, pipes):
            seen[s] = p.stats_snapshot()

    def feed_serial(*args):
        *handlers, rt = args
        assert all(rt.junctions[s]._pipeline is None for s in streams)
        for side, cols, ts in frames:
            handlers[side].send_columns(cols, timestamps=ts)
            rt.flush()

    pipe = _capture(app_pipe, feed_paced, out=out, streams=streams,
                    batch_size=bs)
    serial = _capture(app_serial, feed_serial, out=out, streams=streams,
                      batch_size=bs)
    assert sum(len(b[0]) for b in pipe) > bs  # the case is not vacuous
    _assert_blocks_identical(pipe, serial)
    # every frame's last chunk went on starve, none at a flush; a chunk
    # with the next one's rows already in the ring still overlapped
    assert sum(s["batches_delivered"] for s in seen.values()) == chunks
    assert sum(s["batches_delivered_on_starve"]
               for s in seen.values()) >= len(frames)
    assert all(s["batches_overlapped"] + s["batches_delivered_on_starve"]
               == s["batches_delivered"] for s in seen.values())


class TestConservation:
    """Multi-producer: order is unspecified, accounting is not. Every sent
    event is delivered exactly once or counted as dropped — under the
    pipeline (block policy) and under the fallback ring (drop policies,
    where @Async(workers=) gates back to the MPSC path)."""

    N_PRODUCERS = 4
    PER_PRODUCER = 600

    def _stress(self, app: str, *, expect_pipeline: bool):
        rt = SiddhiManager().create_siddhi_app_runtime(app)
        delivered = [0]
        lock = threading.Lock()

        def cb(b):
            with lock:
                delivered[0] += b.count

        rt.add_callback("OutStream", cb, columnar=True)
        rt.start()
        try:
            assert (rt.junctions["TradeStream"]._pipeline
                    is not None) == expect_pipeline
            h = rt.get_input_handler("TradeStream")
            rows = _rows(self.PER_PRODUCER, seed=21)

            def produce(p):
                tss = np.arange(p * self.PER_PRODUCER,
                                (p + 1) * self.PER_PRODUCER, dtype=np.int64)
                h.send_batch(rows, timestamps=tss)

            threads = [threading.Thread(target=produce, args=(p,))
                       for p in range(self.N_PRODUCERS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            rt.flush()
        finally:
            rt.shutdown()  # drains whatever is still staged
        rep = rt.statistics_report()
        sent = self.N_PRODUCERS * self.PER_PRODUCER
        dropped = sum(rep["ingress_dropped"].get("TradeStream", {}).values())
        discarded = rep["recovery"]["shutdown_discarded"]
        # pass-through query: every admitted row reaches the callback, so
        # conservation is exact — delivered + dropped + discarded == sent
        assert delivered[0] + dropped + discarded == sent
        return delivered[0]

    def test_pipeline_block_policy_conserves(self):
        app = ("@app:name('C1')\n"
               "@Async(buffer.size='128', workers='2', "
               "overflow.policy='block', block.timeout='30 sec')\n"
               "define stream TradeStream "
               "(symbol string, price double, volume long);\n"
               "@info(name='q') from TradeStream "
               "select symbol, price, volume insert into OutStream;")
        self._stress(app, expect_pipeline=True)

    def test_drop_policy_falls_back_and_conserves(self):
        app = ("@app:name('C2')\n"
               "@Async(buffer.size='128', workers='2', "
               "overflow.policy='drop.old', max.staged='512')\n"
               "define stream TradeStream "
               "(symbol string, price double, volume long);\n"
               "@info(name='q') from TradeStream "
               "select symbol, price, volume insert into OutStream;")
        self._stress(app, expect_pipeline=False)


class TestStatisticsSection:
    def test_ingress_pipeline_section_always_present(self):
        """statistics_report() carries the section even for apps with no
        pipeline (empty dict) — dashboards key on it unconditionally."""
        rt = SiddhiManager().create_siddhi_app_runtime(APP_SERIAL)
        try:
            rep = rt.statistics_report()
            assert rep["ingress_pipeline"] == {}
        finally:
            rt.shutdown()

    def test_ingress_pipeline_section_populated(self):
        rt = SiddhiManager().create_siddhi_app_runtime(APP_PIPE)
        rt.start()
        try:
            h = rt.get_input_handler("TradeStream")
            h.send_batch(_rows(100),
                         timestamps=np.arange(100, dtype=np.int64))
            rt.flush()
            rep = rt.statistics_report()
            sec = rep["ingress_pipeline"]["TradeStream"]
            assert sec["workers"] == 2
            assert sec["rows_in"] == 100
            for key in ("ring_depth_hwm", "h2d_overlap_ratio",
                        "worker_utilization", "stage_ms"):
                assert key in sec
        finally:
            rt.shutdown()


# ---------------------------------------- overlapping dictionaries, 4 producers
#
# Four producers post SXF1 frames whose dictionaries overlap (a 10,000-key
# universe, 2,048 rows a frame) through four workers: the extension's
# byte-keyed intern table answers for most values, off the interpreter lock.
# A frame is one run and one batch, so the delivered blocks name the order
# the runs interned in (their tickets); the synchronous path fed the frames
# in that order must give the same codes and the same string table.

WIRE_FRAME = 2048
WIRE_KEYS = 10_000


def _wire_app(asynchronous: bool) -> str:
    return ((f"@app:name('Wire4')\n@Async(buffer.size='{WIRE_FRAME}', "
             "workers='4')\n" if asynchronous else "@app:name('Wire1')\n")
            + "define stream TradeStream "
            "(symbol string, price double, volume long);\n"
            "@info(name='q') from TradeStream "
            "select symbol, price, volume insert into OutStream;")


def _wire_frames(producers: int = 4, per_producer: int = 6):
    """{frame's first timestamp: (producer, cols, ts)}: each frame's rows
    drawn from the shared universe, with nulls, its stamps its own."""
    frames = {}
    for p in range(producers):
        for f in range(per_producer):
            rng = np.random.default_rng([53, p, f])
            keys = rng.integers(0, WIRE_KEYS, WIRE_FRAME).tolist()
            sym = np.array([f"K{k:05d}" for k in keys], dtype=object)
            sym[rng.random(WIRE_FRAME) < 0.01] = None
            ts = np.arange(WIRE_FRAME, dtype=np.int64) + \
                (p * per_producer + f) * 100_000
            frames[int(ts[0])] = (p, {
                "symbol": sym,
                "price": rng.uniform(1.0, 1000.0, WIRE_FRAME),
                "volume": rng.integers(1, 1000, WIRE_FRAME)}, ts)
    return frames


def test_four_producers_overlapping_dictionaries_intern_as_the_serial_path():
    from siddhi_tpu.io import wire
    frames = _wire_frames()
    seen: dict = {}

    def body(h, cols, ts):
        return wire.encode_frames(wire.schema_plan(h.junction.definition),
                                  cols, WIRE_FRAME, ts=ts)

    def feed_producers(h, rt):
        p_stats = _pipeline_of(rt)
        assert p_stats is not None, "pipeline did not engage"
        mine = [[body(h, c, ts) for q, c, ts in frames.values() if q == p]
                for p in range(4)]
        start = threading.Barrier(4)

        def produce(bodies):
            start.wait()
            for b in bodies:
                assert wire.deliver_frames(h, b) == WIRE_FRAME

        threads = [threading.Thread(target=produce, args=(b,))
                   for b in mine]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rt.flush()
        seen["stats"] = p_stats.stats_snapshot()
        seen["strings"] = h.junction.codec.string_tables["symbol"].snapshot()

    pipe = _capture(_wire_app(True), feed_producers)
    assert [len(b[0]) for b in pipe] == [WIRE_FRAME] * len(frames)
    order = [int(b[0][0]) for b in pipe]  # the frames in ticket order
    assert sorted(order) == sorted(frames)
    serial_seen: dict = {}

    def feed_serial(h, rt):
        for first in order:
            _, cols, ts = frames[first]
            assert wire.deliver_frames(h, body(h, cols, ts)) == WIRE_FRAME
            rt.flush()
        serial_seen["strings"] = \
            h.junction.codec.string_tables["symbol"].snapshot()

    serial = _capture(_wire_app(False), feed_serial, batch_size=WIRE_FRAME)
    _assert_blocks_identical(pipe, serial)
    assert seen["strings"] == serial_seen["strings"]
    # each distinct string misses the table at most once; every other value
    # of every frame's dictionary is found in it (none with the Python path)
    stats = seen["stats"]
    distinct = len(seen["strings"]["strings"]) - 1  # code 0 is null
    if native_mod.available():
        assert stats["intern_values"] == sum(
            len({s for s in c["symbol"] if s is not None})
            for _, c, _ in frames.values())
        assert stats["intern_table_hits"] >= \
            stats["intern_values"] - distinct > 0
    else:
        assert stats["intern_values"] == stats["intern_table_hits"] == 0
