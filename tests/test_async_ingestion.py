"""Multithreaded async ingestion (reference: stream/JunctionTestCase —
multi-producer Disruptor publication; StreamJunction.java:279-316)."""

import threading
import time

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu import native as native_mod

pytestmark = pytest.mark.skipif(
    native_mod.native is None, reason="native ring unavailable")


def build(app, **kw):
    rt = SiddhiManager().create_siddhi_app_runtime(app, **kw)
    rt.start()
    return rt


class TestAsyncIngestion:
    def test_multithreaded_producers_all_delivered(self):
        rt = build(
            "@Async(buffer.size='64')\n"
            "define stream S (producer long, seq long);\n"
            "@info(name='q') from S select producer, seq insert into Out;")
        got = []
        lock = threading.Lock()

        def cb(ts, i, r):
            with lock:
                got.extend(tuple(e.data) for e in i or [])

        rt.add_query_callback("q", cb)
        h = rt.get_input_handler("S")
        N, P = 500, 4

        def produce(pid):
            for s in range(N):
                h.send((pid, s))

        threads = [threading.Thread(target=produce, args=(p,))
                   for p in range(P)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rt.flush()  # barrier: drain the ring
        rt.shutdown()
        assert len(got) == N * P
        # per-producer FIFO order survives the multi-producer ring
        for p in range(P):
            seqs = [s for pid, s in got if pid == p]
            assert seqs == list(range(N))

    def test_feeder_delivers_without_explicit_flush(self):
        rt = build(
            "@Async(buffer.size='8')\n"
            "define stream S (v long);\n"
            "@info(name='q') from S select v insert into Out;")
        got = []
        rt.add_query_callback("q", lambda ts, i, r: got.extend(i or []))
        h = rt.get_input_handler("S")
        for i in range(32):
            h.send((i,))
        deadline = time.time() + 5.0
        while len(got) < 32 and time.time() < deadline:
            time.sleep(0.01)
        rt.shutdown()
        assert [e.data[0] for e in got] == list(range(32))

    def test_backpressure_blocks_then_recovers(self):
        rt = build(
            "@Async(buffer.size='4')\n"
            "define stream S (v long);\n"
            "@info(name='q') from S select count() as n insert into Out;")
        got = []
        rt.add_query_callback("q", lambda ts, i, r: got.extend(i or []))
        h = rt.get_input_handler("S")
        # far more than the ring capacity; producers must block, not drop
        for i in range(5000):
            h.send((i,))
        rt.flush()
        rt.shutdown()
        assert got[-1].data[0] == 5000

    def test_sync_streams_unaffected(self):
        rt = build(
            "define stream S (v long);\n"
            "@info(name='q') from S select v insert into Out;")
        assert not rt.junctions["S"].is_async
        got = []
        rt.add_query_callback("q", lambda ts, i, r: got.extend(i or []))
        rt.get_input_handler("S").send((1,))
        rt.flush()
        assert [e.data[0] for e in got] == [1]


class TestAutoFlush:
    """Wall-clock auto-flush (the Disruptor's immediate-consumption role,
    reference StreamJunction.java:68 + Scheduler.java:48): staged rows
    deliver within ~auto_flush_ms with no flush() from the caller."""

    def test_staged_rows_flush_without_caller(self):
        import time

        from siddhi_tpu import SiddhiManager
        app = ("define stream S (v double);\n"
               "from S[v > 0.0] select v insert into Out;")
        rt = SiddhiManager().create_siddhi_app_runtime(
            app, batch_size=256, auto_flush_ms=10)
        got = []
        rt.add_callback("Out", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        rt.get_input_handler("S").send((1.0,))
        t0 = time.perf_counter()
        while not got and time.perf_counter() - t0 < 10:
            time.sleep(0.005)
        rt.shutdown()
        assert got == [(1.0,)]

    def test_annotation_enables_flusher(self):
        from siddhi_tpu import SiddhiManager
        app = ("@app:autoFlush(interval='25 ms')\n"
               "define stream S (v double);\n"
               "from S select v insert into Out;")
        rt = SiddhiManager().create_siddhi_app_runtime(app)
        assert rt.auto_flush_ms == 25
        rt.start()
        assert rt._flusher_thread is not None
        rt.shutdown()
        assert rt._flusher_stop is None


class TestStarveDelivery:
    """The feeder's double buffer (core/ingress.py `_feed_loop`) keeps a
    built batch only while the ring has rows for the next one: the moment
    the feeder would starve it delivers the batch it holds. Every wait here
    has its own deadline; nothing calls `drain()` before the rows are in."""

    BS = 64
    APP = (f"@Async(buffer.size='{BS}', workers='2')\n"
           "define stream S (v long);\n"
           "@info(name='q') from S select v insert into Out;")

    @staticmethod
    def _until(done, seconds: float) -> bool:
        deadline = time.monotonic() + seconds
        while not done():
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.002)
        return True

    @staticmethod
    def _counters(rt) -> tuple:
        sec = rt.statistics_report()["ingress_pipeline"]["S"]
        return (sec["batches_delivered"], sec["batches_overlapped"],
                sec["batches_delivered_on_starve"])

    def _chunk(self, k: int) -> list:
        return [(k * self.BS + i,) for i in range(self.BS)]

    def test_a_lone_full_chunk_is_delivered_without_a_drain(self):
        rt = build(self.APP)
        try:
            assert rt.junctions["S"]._pipeline is not None
            got = []
            rt.add_query_callback(
                "q", lambda ts, i, r: got.extend(e.data[0] for e in i or []))
            rt.get_input_handler("S").send_batch(self._chunk(0))
            # the parent held it until the next chunk or a flush: for ever
            assert self._until(lambda: len(got) == self.BS, 2.0), len(got)
            assert got == list(range(self.BS))
            assert self._counters(rt) == (1, 0, 1)
            # nothing was left behind for the barrier to deliver
            rt.drain()
            assert len(got) == self.BS and self._counters(rt) == (1, 0, 1)
        finally:
            rt.shutdown()

    def test_chunks_waiting_in_the_ring_still_overlap(self):
        """The first delivery stands in a callback while four more chunks
        are published: the feeder finds each one's rows in the ring when it
        comes back, so it uploads the next before delivering the held one,
        and only the last, with nothing behind it, goes on starve."""
        rt = build(self.APP)
        gate = threading.Event()
        got = []

        def cb(ts, i, r):
            got.extend(e.data[0] for e in i or [])
            assert gate.wait(10.0)

        try:
            rt.add_query_callback("q", cb)
            h = rt.get_input_handler("S")
            n = 5
            h.send_batch(self._chunk(0))
            assert self._until(lambda: len(got) == self.BS, 2.0)
            pipe = rt.junctions["S"]._pipeline
            for k in range(1, n):
                h.send_batch(self._chunk(k))
            assert self._until(
                lambda: pipe.ring.size() == (n - 1) * self.BS, 5.0)
            gate.set()
            assert self._until(lambda: len(got) == n * self.BS, 5.0), len(got)
            assert got == list(range(n * self.BS))  # arrival order
            delivered, overlapped, on_starve = self._counters(rt)
            # no flush delivered anything: the two counters are the whole
            assert delivered == n == overlapped + on_starve
            assert overlapped == n - 2 and on_starve == 2
            # a partial tail is the flush's: the third way out
            h.send_batch([(n * self.BS,), (n * self.BS + 1,)])
            rt.drain()
            assert got[-2:] == [n * self.BS, n * self.BS + 1]
            delivered, overlapped, on_starve = self._counters(rt)
            assert delivered - overlapped - on_starve == 1
            assert (overlapped, on_starve) == (n - 2, 2)
        finally:
            gate.set()
            rt.shutdown()

    def test_superstep_staging_bypasses_the_double_buffer(self):
        rt = build("@app:superstep(k='2')\n" + self.APP)
        try:
            got = []
            rt.add_callback("Out", lambda b: got.extend(
                b.column("v").tolist()), columnar=True)
            h = rt.get_input_handler("S")
            h.send_batch(self._chunk(0))
            # one staged chunk of two: staging holds it, as before
            time.sleep(0.2)
            assert got == []
            h.send_batch(self._chunk(1))
            assert self._until(lambda: len(got) == 2 * self.BS, 10.0)
            h.send_batch(self._chunk(2))
            time.sleep(0.2)
            assert len(got) == 2 * self.BS
            rt.drain()
            assert got == list(range(3 * self.BS))
            sec = rt.statistics_report()["ingress_pipeline"]["S"]
            assert sec["superstep_decline"] is None
            assert sec["supersteps_dispatched"] == 1
            assert sec["batches_overlapped"] == 0
            assert sec["batches_delivered_on_starve"] == 0
        finally:
            rt.shutdown()
