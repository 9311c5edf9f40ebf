"""Stage spans (telemetry/tracing.py `Span`, `StageCells`): wait and work
told apart inside the served path. The primitive alone first, then every
site it is entered at (core/ingress.py, io/wire.py, core/stream.py
AsyncDecoder) after a served run through `wire.deliver_frames`, then the
same spans on the profiler's clock and on `/metrics`."""

import glob
import os
import threading
import time

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, compiler
from siddhi_tpu.io import wire
from siddhi_tpu.telemetry import prometheus
from siddhi_tpu.telemetry.tracing import Span, StageCells

pytestmark = pytest.mark.smoke

BS = 64
APP = f"""
@app:name('Spans')
@Async(buffer.size='{BS}', workers='2')
define stream TradeStream (symbol string, price double, volume long);
@info(name='cheap')
from TradeStream[price < 700.0]
select symbol, price, volume
insert into OutStream;
@info(name='dear')
from TradeStream[price >= 700.0]
select symbol, price
insert into DearStream;
"""
NEW_CELLS = ("wire", "claim_wait", "ticket_wait", "intern_lock_wait", "fill",
             "lock_wait", "dispatch", "hold")


def _body(n: int, seed: int) -> bytes:
    rng = np.random.default_rng(seed)
    cols = {"symbol": np.array([f"S{int(k)}" for k in
                                rng.integers(1, 50, n)], dtype=object),
            "price": rng.uniform(1.0, 1000.0, n),
            "volume": rng.integers(1, 100, n).astype(np.int64)}
    plan = wire.schema_plan(
        compiler.parse(APP).stream_definitions["TradeStream"])
    return wire.encode_frames(plan, cols, n, chunk=BS)


def _served_run(frames: int = 6, during=None):
    """A two-query app fed `frames` bodies of 3 frames each through
    deliver_frames, read back by the async decoder. Returns (statistics,
    /metrics text, rows delivered)."""
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(APP, async_callbacks=True)
    got = [0]
    rt.add_callback("OutStream", lambda b: got.__setitem__(
        0, got[0] + b.count), columnar=True)
    rt.start()
    try:
        handler = rt.get_input_handler("TradeStream")
        for f in range(frames):
            assert wire.deliver_frames(handler, _body(3 * BS, f)) == 3 * BS
            if during is not None:
                during(f)
        rt.drain()
        return rt.statistics_report(), prometheus.render_manager(mgr), got[0]
    finally:
        rt.shutdown()


# ---------------------------------------------------------- the primitive


def test_span_books_in_its_threads_slot_and_snapshot_sums_slots():
    cells = StageCells(("work", "other"))

    def run():
        for _ in range(3):
            with cells.span("work", "siddhi.test.work"):
                time.sleep(0.002)

    threads = [threading.Thread(target=run) for _ in range(2)]
    for t in threads:
        t.start()
    with cells.span("work", "siddhi.test.work") as mine:
        time.sleep(0.002)
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert mine.wall_ns >= 2_000_000
    # one slot per writer thread, beside the shared one
    assert len(cells._slots) == 4
    snap = cells.snapshot()
    assert snap["work"]["batches"] == 7
    assert snap["work"]["total_ms"] >= 14.0
    assert snap["work"]["mean_ms"] == pytest.approx(
        snap["work"]["total_ms"] / 7)
    assert snap["other"] == {"total_ms": 0.0, "batches": 0, "mean_ms": 0.0}
    cells.book_shared("other", 5_000_000)
    cells.book("other", 1_000_000, units=2)
    assert cells.snapshot()["other"]["batches"] == 3
    assert cells.snapshot()["other"]["total_ms"] == pytest.approx(6.0)


def test_concurrent_writers_and_a_reader_lose_no_unit():
    """More writers than cores, a short switch interval, and a reader
    taking snapshots meanwhile: every unit and every ns is counted once."""
    import sys
    cells = StageCells(("own", "shared"))
    lock = threading.Lock()
    writers, each = 16, 500
    stop = threading.Event()
    seen = []

    def write():
        for _ in range(each):
            with cells.span("own", "siddhi.test.own"):
                pass
            cells.book("own", 7)
            with lock:
                cells.book_shared("shared", 3)

    def read():
        while not stop.is_set():
            seen.append(cells.snapshot()["own"]["batches"])

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read)
        reader.start()
        threads = [threading.Thread(target=write) for _ in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        stop.set()
        reader.join(timeout=10)
        assert not reader.is_alive()
    finally:
        sys.setswitchinterval(old)
    snap = cells.snapshot()
    assert snap["own"]["batches"] == 2 * writers * each
    assert snap["shared"]["batches"] == writers * each
    assert snap["shared"]["total_ms"] == pytest.approx(
        3 * writers * each / 1e6)
    assert seen == sorted(seen)  # a reader never sees a count go back


def test_cpu_span_books_less_cpu_than_wall_across_a_sleep():
    cells = StageCells(("wait",), cpu=("wait",))
    with cells.span("wait", "siddhi.test.wait"):
        time.sleep(0.05)
        sum(range(20000))
    cell = cells.snapshot()["wait"]
    assert cell["total_ms"] >= 50.0
    assert 0.0 < cell["cpu_ms"] < cell["total_ms"] - 40.0


def test_an_exception_inside_a_span_still_closes_and_books_it():
    cells = StageCells(("work",))
    with pytest.raises(ValueError):
        with cells.span("work", "siddhi.test.work"):
            raise ValueError("inside")
    assert cells.snapshot()["work"]["batches"] == 1
    # begin/end by hand, the loop-shaped form; drop() books nothing
    span = cells.span("work", "siddhi.test.work").begin()
    span.end(units=0)
    cells.span("work", "siddhi.test.work").begin().drop()
    assert cells.snapshot()["work"]["batches"] == 1
    with pytest.raises(KeyError):
        cells.span("typo", "siddhi.test.typo")
    # a span of no cell times itself and books nowhere: the caller does
    with Span("siddhi.test.free", cpu=True, seq=3) as free:
        time.sleep(0.001)
    assert free.wall_ns >= 1_000_000 > free.cpu_ns >= 0


# --------------------------------------------------------------- the sites


def test_every_new_cell_is_booked_by_a_served_run():
    stats, _, rows = _served_run()
    assert rows > 0
    stage = stats["ingress_pipeline"]["TradeStream"]["stage_ms"]
    for name in NEW_CELLS + ("decode", "intern", "h2d", "device"):
        assert stage[name]["total_ms"] > 0 and stage[name]["batches"] > 0, name
    for name in ("wire", "intern", "h2d", "dispatch"):
        assert 0 < stage[name]["cpu_ms"], name
    # per frame, per worker run, per delivered batch
    assert stage["wire"]["batches"] == 18
    assert stage["claim_wait"]["batches"] == stage["decode"]["batches"] \
        == stage["ticket_wait"]["batches"] == 18
    batches = stats["ingress_pipeline"]["TradeStream"]["batches_delivered"]
    assert stage["device"]["batches"] == stage["dispatch"]["batches"] \
        == stage["lock_wait"]["batches"] == stage["h2d"]["batches"] == batches
    # `device` keeps its meaning: the wait for the lock plus the work under it
    parts = stage["lock_wait"]["total_ms"] + stage["dispatch"]["total_ms"]
    assert parts <= stage["device"]["total_ms"] <= 1.05 * parts


def test_hold_is_the_double_buffers_residence():
    stats, _, _ = _served_run(frames=3)
    hold = stats["ingress_pipeline"]["TradeStream"]["stage_ms"]["hold"]
    assert hold["batches"] > 0 and hold["total_ms"] > 0


def test_fill_ends_before_a_starve_delivery_and_hold_is_short():
    """Paced frames, one chunk each, into a callback that takes 100 ms:
    the feeder meets an empty ring after every upload and delivers the
    batch it holds there. `fill` (the feeder waiting for rows) is closed
    before that delivery and reopened after it, so it swallows neither the
    dispatch nor the idle time between frames; `hold` is the few polls from
    the upload's end to the first empty one, not the interval."""
    frames, pause, work = 4, 0.15, 0.1
    rt = SiddhiManager().create_siddhi_app_runtime(APP)
    rows = [0]

    def slow(block):
        rows[0] += block.count
        time.sleep(work)

    rt.add_callback("OutStream", slow, columnar=True)
    rt.start()
    try:
        handler = rt.get_input_handler("TradeStream")
        for f in range(frames):
            assert wire.deliver_frames(handler, _body(BS, f)) == BS
            time.sleep(pause)
        deadline = time.monotonic() + 5.0
        while True:
            sec = rt.statistics_report()["ingress_pipeline"]["TradeStream"]
            if sec["batches_delivered"] == frames:
                break
            assert time.monotonic() < deadline, sec
            time.sleep(0.005)
    finally:
        rt.shutdown()
    assert rows[0] > 0
    assert sec["batches_delivered_on_starve"] == frames
    assert sec["batches_overlapped"] == 0
    stage = sec["stage_ms"]
    assert stage["dispatch"]["total_ms"] >= frames * work * 1e3
    assert stage["fill"]["batches"] == frames  # a unit per chunk, as before
    assert stage["fill"]["total_ms"] < frames * work * 1e3
    assert stage["fill"]["total_ms"] < frames * pause * 1e3
    assert stage["hold"]["batches"] == frames
    assert stage["hold"]["mean_ms"] < 0.2 * pause * 1e3


def test_readback_section_counts_and_times_every_batch():
    stats, _, rows = _served_run()
    back = stats["readback"]
    assert rows > 0 and back["submitted"] == back["delivered"] > 0
    assert set(back["stage_ms"]) == {"queue", "fetch", "deliver"}
    for name, cell in back["stage_ms"].items():
        assert cell["batches"] == back["submitted"], name
        assert cell["total_ms"] > 0, name
    # a runtime with synchronous callbacks has no read-back to report
    rt = SiddhiManager().create_siddhi_app_runtime(APP)
    try:
        assert rt.statistics_report()["readback"] == {}
    finally:
        rt.shutdown()


def test_spans_lie_on_the_profilers_clock(tmp_path):
    import jax
    from jax.profiler import ProfileData

    # the first body compiles outside the session; the rest run inside it
    def during(f):
        if f == 0:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # Python frames: large, unread
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)

    try:
        _served_run(frames=4, during=during)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("siddhi."):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    for name in ("siddhi.front.wire", "siddhi.ingress.claim_wait",
                 "siddhi.ingress.ticket_wait",
                 "siddhi.ingress.intern_lock_wait", "siddhi.ingress.intern",
                 "siddhi.feeder.fill",
                 "siddhi.feeder.h2d", "siddhi.feeder.lock_wait",
                 "siddhi.feeder.dispatch", "siddhi.readback.submit",
                 "siddhi.readback.fetch", "siddhi.readback.callback"):
        assert events.get(name), name
    # and no label beyond the inventory (PERF.md section 3): each of these
    # has a reader under benchmarks/ or a row in docs/OBSERVABILITY.md
    assert set(events) <= {
        "siddhi.front.wire", "siddhi.ingress.claim_wait",
        "siddhi.ingress.ticket_wait", "siddhi.ingress.intern_lock_wait",
        "siddhi.ingress.intern", "siddhi.feeder.fill", "siddhi.feeder.h2d",
        "siddhi.feeder.lock_wait", "siddhi.feeder.dispatch",
        "siddhi.readback.submit", "siddhi.readback.fetch",
        "siddhi.readback.callback", "siddhi.join.step",
        "siddhi.join.drop_sync", "siddhi.pattern.step",
        "siddhi.pattern.drop_sync", "siddhi.window.drop_sync",
        "siddhi.partition.step", "siddhi.partition.drop_sync"}, sorted(events)
    dispatches = events["siddhi.feeder.dispatch"]
    chunks = [int(stats["chunk"]) for _, _, stats in dispatches]
    assert chunks == sorted(chunks) and len(set(chunks)) == len(chunks)
    # the read-back's submit nests in the dispatch that caused it
    for a, z, stats in events["siddhi.readback.submit"]:
        assert "seq" in stats
        assert any(da <= a and z <= dz for da, dz, _ in dispatches)


def test_metrics_exposition_carries_the_new_stage_labels():
    _, text, _ = _served_run(frames=2)
    assert prometheus.validate_exposition(text) == []
    for name in NEW_CELLS:
        assert ('siddhi_ingress_stage_seconds_total{app="Spans",'
                f'stream="TradeStream",stage="{name}"}}') in text, name
