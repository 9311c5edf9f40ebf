"""Stream junctions, input handlers, callbacks — the ingestion/dispatch plane.

Reference: core/stream/StreamJunction.java:64 is a per-stream pub/sub hub backed
by the LMAX Disruptor for async mode. The TPU replacement is a **host-side
columnar micro-batcher**: producers append rows into numpy staging buffers; a
flush converts the staged rows to one device EventBatch and synchronously
delivers it to every receiver (query runtimes consume device batches directly;
stream callbacks decode to host events). Micro-batch size is the backpressure /
latency knob that replaces the Disruptor ring size (StreamJunction.java:68).

Device-to-device chaining: a query whose output feeds another stream publishes
its output EventBatch straight into the target junction (`publish_batch`),
so multi-query pipelines stay on device until a host callback needs decoding.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Optional, Sequence

import numpy as np

from ..errors import SiddhiAppCreationError, SiddhiAppRuntimeError
from ..telemetry.tracing import Span, StageCells, stage
from ..util.locks import named_condition, named_lock, note_blocking
from ..query_api.definition import AttributeType, StreamDefinition
from . import dtypes
from .context import SiddhiAppContext
from .event import Event, EventBatch, EventType, StreamCodec


class Receiver:
    """Junction subscriber (reference: StreamJunction.Receiver)."""

    def on_batch(self, batch: EventBatch, now: int) -> None:
        raise NotImplementedError


class StreamCallback(Receiver):
    """User-facing stream subscriber (reference:
    core/stream/output/StreamCallback.java:38). Subclass and override
    `receive`, or wrap a plain function with FunctionStreamCallback."""

    _junction: "StreamJunction" = None

    def receive(self, events: list[Event]) -> None:
        raise NotImplementedError

    def on_batch(self, batch: EventBatch, now: int) -> None:
        events = batch.to_host_events(self._junction.codec)
        if events:
            self.receive(events)


class FunctionStreamCallback(StreamCallback):
    def __init__(self, fn: Callable[[list[Event]], None]):
        self.fn = fn

    def receive(self, events: list[Event]) -> None:
        self.fn(events)


class ColumnarBlock:
    """One delivered output micro-batch, as columns — the TPU-native analogue
    of the Event[] the reference hands its callbacks (StreamCallback.java:38).

    Columns are compacted numpy arrays in DEVICE dtypes (doubles arrive as
    float32, strings as int32 dictionary codes). `strings(name)` decodes a
    string column to Python values; `to_events()` materializes classic Event
    objects for code that wants them. Batch-level delivery skips per-event
    object construction entirely — on wide batches that is the difference
    between the public callback path keeping up with the device and not."""

    __slots__ = ("timestamps", "columns", "is_expired", "count", "_codec")

    def __init__(self, timestamps, columns, is_expired, count, codec):
        self.timestamps = timestamps  # int64[count]
        self.columns = columns  # name -> numpy[count] (device dtypes)
        self.is_expired = is_expired  # bool[count]
        self.count = count
        self._codec = codec

    def __len__(self) -> int:
        return self.count

    def column(self, name: str) -> np.ndarray:
        return self.columns[name]

    def strings(self, name: str) -> list:
        """Decode a string column's codes to Python strings (lazy — only
        callbacks that read the text pay the decode). Uses the same native
        map_codes fast path as the Event decode."""
        from .event import StringTable
        tbl = self._codec.string_tables[name]
        codes = self.columns[name]
        from .. import native as native_mod
        nat = native_mod.native
        if nat is not None and (codes.size == 0 or
                                int(codes.max()) < StringTable.TRANSIENT_BASE):
            return nat.map_codes(np.ascontiguousarray(codes), tbl._to_str)
        return tbl.decode_array(codes.tolist())

    def to_events(self) -> list[Event]:
        """Materialize classic Event objects — same decode (native
        build_events) as the per-Event callback path."""
        from .event import AttributeType
        from .. import native as native_mod
        nat = native_mod.native
        attrs = self._codec.definition.attributes
        cols = []
        for a in attrs:
            if a.type == AttributeType.OBJECT:
                cols.append([None] * self.count)
            elif a.type == AttributeType.STRING:
                cols.append(self.strings(a.name))
            elif a.type == AttributeType.BOOL:
                cols.append(self.columns[a.name].astype(bool).tolist())
            else:
                cols.append(self.columns[a.name].tolist())
        if nat is not None:
            return nat.build_events(
                Event, np.ascontiguousarray(self.timestamps),
                np.ascontiguousarray(self.is_expired).astype(np.uint8),
                tuple(cols))
        return [Event(t, d, is_expired=e)
                for t, d, e in zip(self.timestamps.tolist(), zip(*cols),
                                   self.is_expired.tolist())]


class BatchStreamCallback(Receiver):
    """Columnar (batch-level) stream subscriber: override `receive_batch`,
    or wrap a function via add_callback(..., columnar=True)."""

    _junction: "StreamJunction" = None

    def receive_batch(self, block: ColumnarBlock) -> None:
        raise NotImplementedError

    def on_batch(self, batch: EventBatch, now: int) -> None:
        import jax

        from .event import EventType
        tree = (batch.ts, batch.valid, batch.types, dict(batch.cols))
        # async delivery hands host numpy (device_get already done by the
        # fetch worker); the sync path hands device arrays — one tree fetch.
        # Multi-host: non-addressable shards need the allgather collective,
        # same as EventBatch.to_host_events
        if any(getattr(leaf, "is_fully_addressable", True) is False
               for leaf in jax.tree_util.tree_leaves(tree)):
            from jax.experimental import multihost_utils
            ts, valid, types, cols = \
                multihost_utils.process_allgather(tree, tiled=True)
        else:
            ts, valid, types, cols = jax.device_get(tree)
        idx = np.nonzero(valid)[0]
        if idx.size == 0:
            return
        block = ColumnarBlock(
            timestamps=ts[idx],
            columns={k: v[idx] for k, v in cols.items()},
            is_expired=(types[idx] == int(EventType.EXPIRED)),
            count=int(idx.size),
            codec=self._junction.codec,
        )
        self.receive_batch(block)


class FunctionBatchCallback(BatchStreamCallback):
    def __init__(self, fn: Callable[[ColumnarBlock], None]):
        self.fn = fn

    def receive_batch(self, block: ColumnarBlock) -> None:
        self.fn(block)


def _wire_pack(batch: EventBatch):
    """Device-side wire packing for callback readbacks: int64 timestamps
    ship as (base + uint32 delta) and valid+types fold into one byte —
    ~28% fewer bytes device→host. (Rounds 1–4 measured the readback as the
    bound on callback throughput, on another runner; not re-measured on a
    directly attached chip.) `over` flags a >49-day timestamp span (then
    the fetch worker re-reads the raw batch instead)."""
    import jax.numpy as jnp
    with stage("emit"):
        big = jnp.int64(1) << jnp.int64(62)
        ts0 = jnp.min(jnp.where(batch.valid, batch.ts, big))
        ts0 = jnp.where(ts0 == big, jnp.int64(0), ts0)
        dts = jnp.where(batch.valid, batch.ts - ts0, 0)
        over = jnp.any(dts > jnp.int64(0xFFFFFFFF)) | jnp.any(dts < 0)
        flags = (batch.types.astype(jnp.uint8) << 1) \
            | batch.valid.astype(jnp.uint8)
        return ts0, dts.astype(jnp.uint32), flags, batch.cols, over


_wire_pack_jit = None


def _wire_unpack(host) -> EventBatch:
    ts0, dts, flags, cols, _over = host
    return EventBatch(
        ts=np.int64(ts0) + dts.astype(np.int64),
        cols=cols,
        valid=(flags & 1).astype(bool),
        types=(flags >> 1).astype(np.int8),
    )


class AsyncDecoder:
    """Background device→host decode pipeline for stream callbacks.

    The reference's Disruptor hands callback work to consumer threads
    (StreamJunction.java:279-316); here the analogous decoupling matters even
    more because a callback decode is a device→host readback. Two stages:

      fetch workers (N)   device_get the batch into host numpy arrays —
                          the readbacks OVERLAP across workers (and release
                          the GIL during the transfer)
      delivery thread (1) decodes + fires callbacks strictly in submit
                          order (a sequence-numbered reorder buffer)

    so pipelined throughput is bounded by bandwidth + Python decode, not by
    round trips × batches.

    Failures surface, they are never papered over: a wire-pack step that
    does not compile raises out of submit() into the junction's error
    handling; a failed readback is routed like a failed callback (@OnError,
    else an ERROR record) and its batch is NOT delivered; drain() raises
    when a decoder thread has died or its deadline passes."""

    N_FETCH = 2

    def __init__(self, maxsize: int = 32) -> None:
        import queue
        import threading

        import jax
        # on the CPU backend device memory IS host memory: packing would
        # add a device pass and save no transfer
        self._pack = jax.default_backend() != "cpu"
        self._q: "queue.Queue" = queue.Queue(maxsize=maxsize)
        #: max decoded-but-undelivered batches held in the reorder buffer
        self._max_lag = max(maxsize, self.N_FETCH + 1)
        self._seq = 0
        self._deliver_next = 0
        #: batches whose callback has returned (or failed and been routed):
        #: what drain() waits for; `_deliver_next` runs one ahead of it
        #: while a callback is running
        self._delivered = 0
        self._buffer: dict = {}
        self._cv = named_condition("stream.decoder")
        self._stopping = False
        # statistics_report()["readback"]: per batch, the wait for a fetch
        # worker, the fetch (the wait for the step to end + D2H + unpack)
        # and the reorder wait + callback
        self.cells = StageCells(("queue", "fetch", "deliver"))
        self._deliverer = threading.Thread(
            target=self._deliver_loop, daemon=True, name="siddhi-decoder")
        self._threads = [
            threading.Thread(target=self._fetch_loop, daemon=True,
                             name=f"siddhi-fetch-{i}")
            for i in range(self.N_FETCH)]
        self._threads.append(self._deliverer)
        for t in self._threads:
            t.start()

    def submit(self, receiver: Receiver, batch: EventBatch, now: int,
               junction: "StreamJunction" = None) -> None:
        import jax
        global _wire_pack_jit
        # nests in the span of the dispatch that caused it (the feeder's)
        with Span("siddhi.readback.submit", seq=self._seq):
            payload = batch
            if self._pack:
                if _wire_pack_jit is None:
                    _wire_pack_jit = jax.jit(_wire_pack)
                payload = (_wire_pack_jit(batch), batch)
            for leaf in jax.tree_util.tree_leaves(
                    payload[0] if isinstance(payload, tuple) else payload):
                start = getattr(leaf, "copy_to_host_async", None)
                if start is not None:
                    start()
            # the bounded put may block under the controller lock; safe
            # because decoder threads never block unboundedly on that lock
            # (the @OnError path acquires it with a timeout) so the queue
            # always drains — see docs/CONCURRENCY.md
            note_blocking("queue.put", allow=("app.controller",))
            self._q.put((self._seq, receiver, payload, now, junction,
                         time.perf_counter_ns()))
            self._seq += 1

    @staticmethod
    def _fetch(payload):
        import jax
        if not isinstance(payload, tuple):
            return jax.device_get(payload)
        packed, raw = payload
        host = jax.device_get(packed)
        if bool(host[4]):  # timestamp span overflow: re-read unpacked
            return jax.device_get(raw)
        return _wire_unpack(host)

    def _fetch_loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            seq, receiver, payload, now, junction, queued_ns = item
            self.cells.book("queue", time.perf_counter_ns() - queued_ns)
            err = None
            try:
                with self.cells.span("fetch", "siddhi.readback.fetch",
                                     seq=seq):
                    host = self._fetch(payload)
            except Exception as e:  # noqa: BLE001 — routed by the deliverer
                # a failed readback takes the failed-callback route, in
                # submit order; the sequence number must still publish or
                # everything behind it strands
                err = e
                host = payload[1] if isinstance(payload, tuple) else payload
            with self._cv:
                # backpressure the fetch→deliver stage too: the input
                # queue only bounds submit→fetch, so a slow delivery
                # thread would otherwise grow _buffer without limit.
                # Safe from deadlock: at most N_FETCH seqs are in
                # flight, every seq below the smallest in-flight one is
                # already buffered/delivered, so delivery always
                # progresses and notifies — unless the delivery thread is
                # gone, which drain() reports.
                while (seq - self._deliver_next > self._max_lag
                       and not self._stopping):
                    if not self._deliverer.is_alive():
                        return
                    self._cv.wait(timeout=0.2)
                self._buffer[seq] = (receiver, host, now, junction, err,
                                     time.perf_counter_ns())
                self._cv.notify_all()

    def _deliver_loop(self) -> None:
        while True:
            with self._cv:
                while (self._deliver_next not in self._buffer
                       and not self._stopping):
                    self._cv.wait(timeout=0.2)
                if self._stopping and self._deliver_next not in self._buffer:
                    return
                receiver, host, now, junction, err, fetched_ns = \
                    self._buffer.pop(self._deliver_next)
                seq = self._deliver_next
                self._deliver_next += 1
            try:
                if err is not None:
                    raise err
                with Span("siddhi.readback.callback", seq=seq):
                    receiver.on_batch(host, now)
                self.cells.book("deliver",
                                time.perf_counter_ns() - fetched_ns)
            except Exception as e:  # noqa: BLE001 — async path must not die
                what = ("async readback failed" if err is not None
                        else "async stream callback failed")
                # preserve @OnError semantics (reference:
                # StreamJunction.java:371-463): route the failed batch like
                # the synchronous _deliver would, under the controller lock
                if junction is not None and (
                        junction.on_error is not None
                        or junction.on_error_action is not None):
                    # BOUNDED acquire, never a plain `with`: a producer can
                    # hold the controller lock while blocked on the bounded
                    # submit queue above — if this thread then waited on the
                    # same lock forever, nothing would drain the reorder
                    # buffer and the whole pipeline would wedge. Timing out
                    # keeps delivery moving (the buffer empties, the
                    # producer's put completes) at the cost of routing this
                    # one failure through the plain log.
                    got = junction.ctx.controller_lock.acquire(timeout=1.0)
                    if got:
                        try:
                            if junction.on_error is not None:
                                junction.on_error(e, host)
                            else:
                                junction._handle_error(e, host, now)
                        except Exception:  # pragma: no cover
                            logging.getLogger("siddhi_tpu").exception(
                                "async @OnError routing failed")
                        finally:
                            junction.ctx.controller_lock.release()
                    else:
                        logging.getLogger("siddhi_tpu").exception(
                            "%s; @OnError routing skipped (controller "
                            "lock busy): %s", what, e)
                else:
                    logging.getLogger("siddhi_tpu").exception(what)
            with self._cv:
                self._delivered = seq + 1
                self._cv.notify_all()

    def drain(self, timeout: float = 120.0) -> None:
        """Block until every submitted batch has been decoded+delivered: the
        last one's callback has returned, not merely begun.
        Raises SiddhiAppRuntimeError when a decoder thread has died or
        `timeout` seconds pass first, naming the sequence number delivery
        is stuck on: a fetch worker lost mid-batch strands its sequence
        number, and waiting for it without a bound never returns."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._delivered < self._seq:
                dead = [t.name for t in self._threads if not t.is_alive()]
                if dead or time.monotonic() >= deadline:
                    why = (f"decoder thread(s) {dead} died" if dead else
                           f"no progress within {timeout:.0f}s")
                    raise SiddhiAppRuntimeError(
                        f"async decoder drain: {why}; stuck at sequence "
                        f"{self._delivered} of {self._seq} submitted "
                        f"({len(self._buffer)} fetched out of order, "
                        f"{self._q.qsize()} queued)")
                self._cv.wait(timeout=0.2)

    def stats_snapshot(self) -> dict:
        return {"stage_ms": self.cells.snapshot(), "submitted": self._seq,
                "delivered": self._delivered}

    def stop(self) -> None:
        """Drain, then stop the threads. A drain that raises still tears
        the threads down before the error reaches the caller."""
        import queue
        try:
            self.drain()
        finally:
            with self._cv:
                self._stopping = True
                self._cv.notify_all()
            for _ in range(self.N_FETCH):
                try:
                    self._q.put_nowait(None)
                except queue.Full:  # dead workers left it full: daemons
                    break
            for t in self._threads:
                t.join(timeout=30)


class StreamJunction:
    """Per-stream hub: staging buffers + receiver fan-out."""

    def __init__(self, definition: StreamDefinition, ctx: SiddhiAppContext,
                 codec: Optional[StreamCodec] = None) -> None:
        self.definition = definition
        self.ctx = ctx
        self.codec = codec or StreamCodec(definition, ctx.global_strings)
        self.receivers: list[Receiver] = []
        self.batch_size = ctx.effective_batch_size
        # @Async: the reference switches to a Disruptor ring with worker
        # consumers (StreamJunction.java:104-134, 279-316). Here:
        # buffer.size tunes the micro-batch AND, once the app starts, a C
        # MPSC staging ring (native/columnar.c) + feeder thread decouple
        # producers from the controller — send() stages in O(1) and the
        # feeder encodes/dispatches batches under the controller lock.
        ann = definition.annotation("async") if definition.annotations else None
        self.is_async = ann is not None
        self._ring = None
        self._ring_cap = 0
        self._feeder = None
        self._feeder_stop = None
        self._feeder_wake = None
        if ann is not None:
            bs = ann.element("buffer.size")
            if bs:
                self.batch_size = int(bs)
            self._ring_cap = max(4 * self.batch_size, 1024)
        # @Async(workers='N') — parallel ingress pipeline (core/ingress.py):
        # N decode/intern workers + a lock-free columnar ring + a
        # double-buffering feeder replace the MPSC ring. Opt-in per stream
        # via the annotation (reference parity: @Async's workers element) or
        # app-wide via SIDDHI_INGRESS_WORKERS; start_async gates on the
        # policies the pipeline cannot honor (WAL, taps, drop policies,
        # OBJECT attrs) and falls back to the MPSC ring.
        self._pipeline = None
        self.ingress_workers = 0
        if ann is not None:
            w = ann.element("workers")
            if w:
                self.ingress_workers = int(w)
            if self.ingress_workers == 0:
                import os as _os
                self.ingress_workers = int(
                    _os.environ.get("SIDDHI_INGRESS_WORKERS", "0") or 0)
        # --- overload protection (bounded ingress + backpressure signal) ---
        # @Async(buffer.size=N, overflow.policy=..., max.staged=...,
        #        block.timeout='1 sec', high.watermark=0.8, low.watermark=0.2)
        # caps staged rows with a pluggable policy for what a full buffer
        # sheds (reference: the Disruptor ring IS the bound; OverflowPolicy
        # here generalizes its blocking wait strategy):
        #   block     producers wait for room (MPSC ring path; default) —
        #             block.timeout bounds the wait, expiry drops + counts
        #   drop.new  shed the arriving row
        #   drop.old  evict the oldest staged row to admit the new one
        #   fault     divert the arriving row to the `!stream` fault stream
        #             or the ErrorStore (replayable), like @OnError
        # Watermarks pace attached sources: staged depth >= high*capacity
        # calls pause() on every attached Source, <= low*capacity resumes.
        self.capacity: Optional[int] = None
        self.overflow_policy = "block"
        self.block_timeout_s: Optional[float] = None
        self.high_watermark = 0.8
        self.low_watermark = 0.2
        #: sources feeding this junction (wiring registers them) — the
        #: pause()/resume() backpressure targets
        self.attached_sources: list = []
        self._bp_paused = False
        if ann is not None:
            pol = (ann.element("overflow.policy") or "block").lower()
            if pol not in ("block", "drop.new", "drop.old", "fault"):
                raise SiddhiAppCreationError(
                    f"@Async on {definition.id!r}: overflow.policy {pol!r} "
                    "must be block | drop.new | drop.old | fault")
            self.overflow_policy = pol
            ms = ann.element("max.staged")
            self.capacity = int(ms) if ms else self._ring_cap
            if self.capacity < self.batch_size and pol != "block":
                raise SiddhiAppCreationError(
                    f"@Async on {definition.id!r}: max.staged "
                    f"({self.capacity}) must be >= buffer.size "
                    f"({self.batch_size})")
            bt = ann.element("block.timeout")
            if bt:
                from .partition import _parse_annotation_time
                self.block_timeout_s = _parse_annotation_time(bt) / 1000.0
            hw = ann.element("high.watermark")
            lw = ann.element("low.watermark")
            self.high_watermark = float(hw) if hw else 0.8
            self.low_watermark = float(lw) if lw else 0.2
            if not 0.0 <= self.low_watermark < self.high_watermark <= 1.0:
                raise SiddhiAppCreationError(
                    f"@Async on {definition.id!r}: need "
                    "0 <= low.watermark < high.watermark <= 1")
        self._staged_rows: list = []
        self._staged_ts: list[int] = []
        #: send-order interceptors fn(ts, data) — multi-stream sequence
        #: queries tap their source junctions to build a merged arrival
        #: stream that preserves TRUE per-event send order across streams
        #: (the reference's sequence receivers consume streams in arrival
        #: order, core/query/input/stream/state/receiver/)
        self.taps: list[Callable] = []
        #: thread-safe pre-staging: a list of (ts, row) tuples appended from
        #: producer threads via stage_row() under its own small lock (an
        #: unlocked append could land on a list flush() just swapped out and
        #: drained — a silently lost event), drained into the staging
        #: buffers under the controller lock at flush
        self._tap_queue: list = []
        self._tap_lock = named_lock("junction.tap")
        self.on_error: Optional[Callable] = None
        #: write-ahead event journal (state/wal.py) — attached by the app
        #: runtime to INGRESS junctions only (user-defined streams). Rows
        #: are journaled before they enter the staging buffers; derived
        #: streams chain on device via publish_batch and are reproducible
        #: from their inputs, so they never journal.
        self.wal = None
        # per-THREAD re-entrancy guards (flushing during callbacks; drain
        # nesting): shared booleans would make one thread's activity no-op
        # another thread's barrier
        import threading as _threading
        self._reentry = _threading.local()
        # @OnError(action=LOG|STREAM|STORE) (reference:
        # StreamJunction.java:371-463, OnErrorAction); None = propagate
        on_error_ann = (definition.annotation("OnError")
                        if definition.annotations else None)
        self.on_error_action: Optional[str] = (
            (on_error_ann.element("action") or "log").lower()
            if on_error_ann is not None else None)
        #: fault junction (`!stream`), created by the app runtime for
        #: action=STREAM; schema = this stream's attrs + _error string
        self.fault_junction: Optional["StreamJunction"] = None
        #: blue-green cutover (core/upgrade.py): when set, every send into
        #: this junction forwards to the v2 junction with the ORIGINAL
        #: (pre-interning) values — v1 and v2 own separate string tables,
        #: so encoded columns/codes must never cross the boundary
        self._redirect: Optional["StreamJunction"] = None
        #: event-time gate (core/event_time.py) — attached by the app
        #: runtime when @app:eventTime names an attribute of this stream;
        #: interposes at _flush_rows so delivery is sorted by event time
        #: and watermark-older rows divert to the ErrorStore (kind="late")
        self._et = None

    def _pad_cap(self, m: int) -> int:
        """Delivery capacity for `m` staged rows: the smallest power-of-two
        lane bucket holding them (shape-bucketed dispatch — each query step
        then compiles at most one executable per ladder rung instead of
        paying the full-capacity kernel for near-empty batches), or the full
        batch size when bucketing is off / the app runs on a device mesh
        (bucket widths must stay mesh-aligned)."""
        if dtypes.config.shape_buckets and self.ctx.mesh is None:
            return dtypes.bucket_capacity(m, self.batch_size)
        return self.batch_size

    # ------------------------------------------------------------- subscribe

    def subscribe(self, receiver: Receiver) -> None:
        if isinstance(receiver, (StreamCallback, BatchStreamCallback)):
            receiver._junction = self
        self.receivers.append(receiver)

    # -------------------------------------------------------------- redirect

    def redirect_to(self, target: Optional["StreamJunction"]) -> None:
        """Atomically route every subsequent send into `target` (the v2
        junction during a blue-green upgrade; None undoes it on rollback).
        Callers set it under the controller lock with this junction quiesced
        (sources paused, async machinery stopped, staged rows flushed)."""
        self._redirect = target

    def _resolve_redirect(self) -> "StreamJunction":
        j = self
        while j._redirect is not None:
            j = j._redirect
        return j

    # ---------------------------------------------------------------- ingest

    def stage_row(self, ts: int, data: Sequence) -> None:
        """Thread-safe staging from arbitrary producer threads; rows enter
        the real staging buffers under the controller lock at the next
        flush. Used by sequence taps, which run on whichever thread called
        the source's send()."""
        with self._tap_lock:
            self._tap_queue.append((ts, data))
            full = len(self._tap_queue) >= self.batch_size
        self.ctx.timestamp_generator.observe_event_time(ts)
        if full:
            self.flush()

    def send_row(self, ts: int, data: Sequence) -> None:
        if self._redirect is not None:
            return self._resolve_redirect().send_row(ts, data)
        if self.wal is not None and not self._lock_owned():
            # journal+stage must be ONE atomic step w.r.t. persist()'s
            # snapshot+rotate critical section: interleaving there would
            # journal the row into the pre-snapshot segment, stage it after
            # the snapshot, and rotate its record away — lost on the next
            # crash. The controller lock is that atomicity (persist holds
            # it); durability mode trades the lock-free @Async ring for it
            # (_lock_owned() skips the ring path below).
            with self.ctx.controller_lock:
                return self.send_row(ts, data)
        if self.wal is not None:  # write-AHEAD: journal before acceptance
            self.wal.append_rows(self.definition.id, (ts,), (tuple(data),))
        for tap in self.taps:
            tap(ts, data)
        if self._bounded_mode() and not self._lock_owned():
            self.ctx.timestamp_generator.observe_event_time(ts)
            self._stage_bounded(((ts, tuple(data)),))
            return
        if self._pipeline is not None and not self._lock_owned():
            self.ctx.timestamp_generator.observe_event_time(ts)
            if self._pipeline.submit_rows((ts,), (tuple(data),)) == 1:
                return
            # pipeline stopping: fall through to synchronous staging
        if self._ring is not None and not self._lock_owned():
            self.ctx.timestamp_generator.observe_event_time(ts)
            # blocking backpressure when the ring is full, like the
            # Disruptor's blocking wait strategy. No per-send wake: the
            # feeder polls at 1 ms, and an Event.set() per row costs more
            # than the stage itself. Re-read the ring each spin: shutdown
            # detaches it, and late sends must fall back to the sync path.
            # block.timeout bounds the wait; expiry sheds the row, counted.
            push = self._ring_push
            deadline = (None if self.block_timeout_s is None
                        else time.monotonic() + self.block_timeout_s)
            while True:
                ring = self._ring
                if ring is None:
                    break
                if push(ring, ts, tuple(data)):
                    if self.attached_sources and not self._bp_paused:
                        self._check_pause(self._ring_size(ring))
                    return
                if deadline is not None and time.monotonic() >= deadline:
                    self.ctx.statistics.track_ingress_drop(
                        self.definition.id, "block.timeout", 1)
                    return
                self._feeder_wake.set()
                time.sleep(0.0002)
        if getattr(self.ctx, "autoflush_active", False) \
                and not self._lock_owned():
            # an auto-flush daemon may swap the staged lists concurrently:
            # the ts+row pair must land atomically w.r.t. that swap
            with self.ctx.controller_lock:
                self._staged_ts.append(ts)
                self._staged_rows.append(data)
        else:
            self._staged_ts.append(ts)
            self._staged_rows.append(data)
        self.ctx.timestamp_generator.observe_event_time(ts)
        if len(self._staged_rows) >= self.batch_size:
            self.flush()

    def send_rows(self, tss: Sequence[int], rows: Sequence) -> None:
        """Batched staging: one call stages many rows (InputHandler.send_batch).
        Per-row Python overhead (call dispatch, watermark observe, size check)
        is paid once per batch instead of once per event."""
        if not rows:
            return
        if self._redirect is not None:
            return self._resolve_redirect().send_rows(tss, rows)
        if self.taps:  # sequence taps need true per-row send order
            for ts, row in zip(tss, rows):
                self.send_row(ts, row)  # journals per row when WAL is on
            return
        if self.wal is not None and not self._lock_owned():
            with self.ctx.controller_lock:  # see send_row: atomic vs persist
                return self.send_rows(tss, rows)
        if self.wal is not None:  # one journal record for the whole batch
            self.wal.append_rows(self.definition.id, tss, rows)
        self.ctx.timestamp_generator.observe_event_time(int(max(tss)))
        if self._bounded_mode() and not self._lock_owned():
            self._stage_bounded((ts, tuple(row))
                                for ts, row in zip(tss, rows))
            return
        if self._pipeline is not None and not self._lock_owned():
            done = self._pipeline.submit_rows(tss, rows)
            if done >= len(rows):
                return
            # pipeline stopping mid-batch: the unconsumed remainder falls
            # back to synchronous staging (claimed prefix is in flight)
            tss, rows = tss[done:], rows[done:]
        if self._ring is not None and not self._lock_owned():
            push = self._ring_push
            for i, (ts, row) in enumerate(zip(tss, rows)):
                pushed = False
                while True:
                    ring = self._ring
                    if ring is None:
                        break
                    if push(ring, ts, tuple(row)):
                        pushed = True
                        break
                    self._feeder_wake.set()
                    time.sleep(0.0002)
                if not pushed:
                    # ring detached mid-batch (shutdown): only the
                    # remainder falls back to synchronous staging — rows
                    # already pushed will be drained by stop_async
                    tss, rows = tss[i:], rows[i:]
                    break
            else:
                return
        if getattr(self.ctx, "autoflush_active", False) \
                and not self._lock_owned():
            with self.ctx.controller_lock:
                self._staged_ts.extend(tss)
                self._staged_rows.extend(rows)
        else:
            self._staged_ts.extend(tss)
            self._staged_rows.extend(rows)
        if len(self._staged_rows) >= self.batch_size:
            self.flush()

    def send_column_batch(self, ts_arr: np.ndarray,
                          cols: dict[str, np.ndarray], n: int) -> None:
        """Columnar ingestion (InputHandler.send_columns): pre-encoded numpy
        columns enter the pipeline with zero per-row host work — chunked to
        the junction's compiled batch capacity and delivered directly."""
        if n == 0:
            return
        self.ctx.timestamp_generator.observe_event_time(int(ts_arr[:n].max()))
        cap = self.batch_size
        tele = getattr(self.ctx, "telemetry", None)
        tracing = tele is not None and tele.on
        with self.ctx.controller_lock:
            note_blocking("device.dispatch", allow=("app.controller",))
            self.flush()  # staged rows first: preserve arrival order
            now = self.ctx.timestamp_generator.current_time()
            for start in range(0, n, cap):
                t0 = time.perf_counter_ns() if tracing else 0
                m = min(cap, n - start)
                if m == cap:
                    ts_c = ts_arr[start:start + cap]
                    cols_c = {k: v[start:start + cap] for k, v in cols.items()}
                else:
                    pcap = self._pad_cap(m)
                    ts_c = np.empty(pcap, dtype=np.int64)
                    ts_c[:m] = ts_arr[start:start + m]
                    ts_c[m:] = ts_arr[start + m - 1]  # monotone pad
                    cols_c = {}
                    for k, v in cols.items():
                        pad = np.zeros(pcap, dtype=v.dtype)
                        pad[:m] = v[start:start + m]
                        cols_c[k] = pad
                if tracing:
                    h2d_t0 = time.perf_counter_ns()
                    batch = EventBatch.from_numpy(ts_c, cols_c, m)
                    trace = tele.mint(self.definition.id, m, t0=t0)
                    trace.h2d_ns = time.perf_counter_ns() - h2d_t0
                    batch._trace = trace
                    tele.record_lag(self.definition.id, int(ts_c[m - 1]))
                else:
                    batch = EventBatch.from_numpy(ts_c, cols_c, m)
                self._deliver(batch, now)

    # ------------------------------------------------------------ async mode

    def _lock_owned(self) -> bool:
        """True when THIS thread already holds the controller lock (a
        callback inside _deliver sending into an async stream): pushing to
        the ring there can deadlock — the only drainer needs the lock we
        hold — so those sends take the synchronous staging path."""
        try:
            return self.ctx.controller_lock._is_owned()
        except AttributeError:  # pragma: no cover — non-CPython RLock
            return getattr(self._reentry, "flushing", False) or \
                getattr(self._reentry, "draining", False)

    # ------------------------------------------------- bounded ingress (drop)

    def _bounded_mode(self) -> bool:
        """True when this junction runs producer-side admission control: a
        capacity with a non-block policy. Rows then enter the thread-safe
        pre-staging queue only (no inline flush, no MPSC ring — the ring's
        blocking push IS the block policy), and delivery is pull-driven by
        the feeder / auto-flusher / explicit flush(), so the bound — not
        delivery speed — caps host memory."""
        return self.capacity is not None and self.overflow_policy != "block"

    def _stage_bounded(self, items) -> None:
        """Admission control for drop/fault policies: each (ts, row) either
        enters the pre-staging queue or is shed per the policy, with every
        decision counted — the drop counters are exact by construction."""
        stats = self.ctx.statistics
        cap = self.capacity
        policy = self.overflow_policy
        diverted: list = []  # fault policy: routed outside the lock
        with self._tap_lock:
            q = self._tap_queue
            for ts, row in items:
                if len(q) < cap:
                    q.append((ts, row))
                elif policy == "drop.old":
                    q.pop(0)
                    q.append((ts, row))
                    stats.track_ingress_drop(self.definition.id, "drop.old", 1)
                elif policy == "drop.new":
                    stats.track_ingress_drop(self.definition.id, "drop.new", 1)
                else:  # fault
                    diverted.append((ts, row))
            depth = len(q)
        stats.track_queue_depth(self.definition.id, depth)
        if diverted:
            stats.track_ingress_drop(self.definition.id, "fault",
                                     len(diverted))
            self._divert_overflow(diverted)
        self._check_pause(depth)
        if self._feeder_wake is not None:
            self._feeder_wake.set()

    def _divert_overflow(self, rows: list) -> None:
        """`overflow.policy='fault'`: overflow rows leave through the same
        doors failed events do — the `!stream` fault junction when one
        exists, else the ErrorStore (replayable), else the log. Never
        silent: the `fault` drop counter is bumped by the caller either way."""
        msg = (f"ingress overflow: {self.definition.id!r} staging buffer "
               f"full (capacity={self.capacity})")
        if self.fault_junction is not None:
            for ts, row in rows:
                self.fault_junction.send_row(ts, tuple(row) + (msg,))
            self.fault_junction.flush()
            return
        store = getattr(self.ctx, "error_store", None)
        if store is not None:
            store.save(self.ctx.name, self.definition.id,
                       [(ts, tuple(row)) for ts, row in rows], msg,
                       kind="overflow")
            return
        logging.getLogger("siddhi_tpu").warning(
            "%s; %d row(s) dropped (no fault stream or error store to "
            "divert to)", msg, len(rows))

    # ------------------------------------------- backpressure (pause/resume)

    def _check_pause(self, depth: int) -> None:
        """High-watermark crossing pauses every attached source (reference:
        Source.pause:113-153 — the transport stops/pausing its consumer).
        Idempotent until the matching low-watermark resume."""
        if (self._bp_paused or not self.attached_sources
                or self.capacity is None):
            return
        if depth >= self.high_watermark * self.capacity:
            with self._tap_lock:  # exact pause/resume counts under races
                if self._bp_paused:
                    return
                self._bp_paused = True
            self.ctx.statistics.track_pause(self.definition.id)
            for s in self.attached_sources:
                try:
                    s.pause()
                except Exception:  # pragma: no cover — transport hiccup
                    logging.getLogger("siddhi_tpu").exception(
                        "pause() failed on source of %r", self.definition.id)

    def _staged_depth(self) -> int:
        depth = len(self._tap_queue) + len(self._staged_rows)
        ring = self._ring
        if ring is not None:
            depth += self._ring_size(ring)
        if self._pipeline is not None:
            depth += self._pipeline.size()
        return depth

    def _ring_size(self, ring) -> int:
        from .. import native as native_mod
        return native_mod.native.ring_size(ring)

    def _maybe_resume(self) -> None:
        """Low-watermark crossing resumes paused sources (their buffered
        payloads re-deliver through on_payload, re-entering admission).
        Called after every flush — the only place depth shrinks."""
        if not self._bp_paused or self.capacity is None:
            return
        if self._staged_depth() <= self.low_watermark * self.capacity:
            with self._tap_lock:  # pair of _check_pause's guarded flip
                if not self._bp_paused:
                    return
                self._bp_paused = False
            self.ctx.statistics.track_resume(self.definition.id)
            for s in self.attached_sources:
                try:
                    s.resume()
                except Exception:  # pragma: no cover — transport hiccup
                    logging.getLogger("siddhi_tpu").exception(
                        "resume() failed on source of %r", self.definition.id)

    def start_async(self) -> None:
        """Spin up the staging ring + feeder thread (app start; reference:
        StreamJunction.startProcessing starting the Disruptor)."""
        from .. import native as native_mod
        if not self.is_async or self._feeder is not None \
                or self._pipeline is not None:
            return
        if (self.ingress_workers > 0 and self.overflow_policy == "block"
                and self.wal is None and not self.taps
                and self._et is None
                and not self.codec.object_attrs):
            from .ingress import IngressPipeline
            try:
                self._pipeline = IngressPipeline(self, self.ingress_workers)
                self._pipeline.start()
                return
            except Exception:
                logging.getLogger("siddhi_tpu").exception(
                    "@Async(workers=%d) on %r: ingress pipeline failed to "
                    "start; falling back to the staging ring",
                    self.ingress_workers, self.definition.id)
                self._pipeline = None
        if self._bounded_mode():
            # drop/fault policies: producer-side accounting must stay exact,
            # so no MPSC ring — a plain feeder drains the bounded pre-staging
            # queue (the ring's blocking push is the block policy's engine)
            import threading
            self._feeder_stop = threading.Event()
            self._feeder_wake = threading.Event()
            self._feeder = threading.Thread(
                target=self._bounded_feed_loop, daemon=True,
                name=f"siddhi-feeder-{self.definition.id}")
            self._feeder.start()
            return
        if native_mod.native is None:
            logging.getLogger("siddhi_tpu").info(
                "@Async on %r: native ring unavailable (no C toolchain); "
                "staying synchronous", self.definition.id)
            return
        import threading
        self._ring_push = native_mod.native.ring_push
        self._ring = native_mod.native.ring_new(self._ring_cap)
        self._feeder_stop = threading.Event()
        self._feeder_wake = threading.Event()
        self._feeder = threading.Thread(
            target=self._feed_loop, daemon=True,
            name=f"siddhi-feeder-{self.definition.id}")
        self._feeder.start()

    def stop_async(self) -> None:
        if self._pipeline is not None:
            # detach FIRST: producers mid-submit fall back to the
            # synchronous staging path; stop() then delivers everything
            # already claimed (workers finish the queue, feeder flushes)
            p, self._pipeline = self._pipeline, None
            p.stop()
        if self._feeder is None:
            return
        self._feeder_stop.set()
        self._feeder_wake.set()
        # detach FIRST: producers mid-spin fall back to the synchronous
        # staging path instead of landing rows in a ring nobody will drain
        ring, self._ring = self._ring, None
        # generous: the feeder may sit inside a first-compile (~40 s on TPU)
        self._feeder.join(timeout=120)
        if self._feeder.is_alive():  # pragma: no cover — wedged device step
            logging.getLogger("siddhi_tpu").warning(
                "async feeder for %r did not stop; leaving its ring "
                "attached (a second consumer would race it)",
                self.definition.id)
            return
        # feeder is gone: drain anything still staged (under the lock so a
        # concurrent user flush cannot become a second consumer)
        with self.ctx.controller_lock:
            self._drain_ring(ring=ring)
        self._feeder = None

    def _feed_loop(self) -> None:
        from .. import native as native_mod
        n = native_mod.native
        while not self._feeder_stop.is_set():
            ring = self._ring
            if ring is None:  # detached by shutdown
                break
            if n.ring_size(ring) == 0:
                self._feeder_wake.wait(timeout=0.001)
                self._feeder_wake.clear()
                continue
            try:
                with self.ctx.controller_lock:
                    self._drain_ring(max_batches=4, ring=ring)
            except Exception:  # pragma: no cover — surfaced via @OnError/log
                logging.getLogger("siddhi_tpu").exception(
                    "async feeder error on %r", self.definition.id)

    def _bounded_feed_loop(self) -> None:
        """Drainer for bounded (drop/fault-policy) junctions: flush whenever
        the pre-staging queue holds rows. Overload shows up as the queue
        pinned at capacity with the policy counters climbing — never as
        unbounded host memory."""
        while not self._feeder_stop.is_set():
            if not self._tap_queue:
                self._feeder_wake.wait(timeout=0.001)
                self._feeder_wake.clear()
                continue
            try:
                self.flush()
            except Exception:  # pragma: no cover — surfaced via @OnError/log
                logging.getLogger("siddhi_tpu").exception(
                    "bounded feeder error on %r", self.definition.id)

    def _drain_ring(self, max_batches: Optional[int] = None,
                    ring=None) -> None:
        """Pop ring entries into the staging buffers and flush as batches.
        Single-consumer discipline: callers hold the controller lock. Owns
        the _draining flag so the nested flush() calls cannot re-enter the
        drain (which would defeat max_batches and hold the lock unbounded)."""
        from .. import native as native_mod
        ring = ring if ring is not None else self._ring
        if ring is None or getattr(self._reentry, "draining", False):
            return
        n = native_mod.native
        self._reentry.draining = True
        try:
            batches = 0
            while max_batches is None or batches < max_batches:
                tss, rows = n.ring_pop_batch(ring, self.batch_size)
                if not rows:
                    break
                self._staged_ts.extend(tss)
                self._staged_rows.extend(rows)
                self.flush()
                batches += 1
        finally:
            self._reentry.draining = False

    def publish_batch(self, batch: EventBatch, now: int) -> None:
        """Device-side publication (query output chaining). Staged host rows
        are flushed first to preserve arrival order."""
        with self.ctx.controller_lock:
            if self.taps:
                # taps need host rows; only derived streams feeding a
                # multi-stream sequence pay this decode
                for ev in batch.to_host_events(self.codec):
                    for tap in self.taps:
                        tap(ev.timestamp, tuple(ev.data))
            if self._staged_rows:
                self.flush()
            self._deliver(batch, now)

    # ----------------------------------------------------------------- flush

    def flush(self, now: Optional[int] = None) -> None:
        if getattr(self._reentry, "flushing", False):
            # same-thread re-entrant flush (a callback sending into its own
            # stream): defer to the outer delivery
            return
        if self._redirect is not None:
            # cutover leftovers (rows a producer staged while racing the
            # swap) forward to the v2 junction as ORIGINAL rows — v2
            # re-journals and re-encodes them under its own codec — then the
            # flush itself delegates
            target = self._resolve_redirect()
            with self.ctx.controller_lock:
                if self._tap_queue:
                    with self._tap_lock:
                        q, self._tap_queue = self._tap_queue, []
                    for ts, row in q:
                        self._staged_ts.append(ts)
                        self._staged_rows.append(row)
                if self._staged_rows:
                    rows, tss = self._staged_rows, self._staged_ts
                    self._staged_rows, self._staged_ts = [], []
                    target.send_rows(tss, rows)
            return target.flush(now)
        if self._pipeline is not None and not self._lock_owned():
            # barrier: every row submitted to the parallel pipeline before
            # this flush is delivered before it returns. Lock-holding
            # callers (auto-flusher, heartbeat, callbacks) skip the barrier
            # — the feeder needs the controller lock to make progress.
            self._pipeline.drain()
        # the staged-list swap and delivery run under the controller lock:
        # the feeder thread extends/flushes the same lists
        with self.ctx.controller_lock:
            if self._ring is not None and not getattr(self._reentry,
                                                      "draining", False):
                self._drain_ring()
            if self._tap_queue:
                with self._tap_lock:
                    q, self._tap_queue = self._tap_queue, []
                for ts, row in q:
                    self._staged_ts.append(ts)
                    self._staged_rows.append(row)
            if self._staged_rows:
                rows, tss = self._staged_rows, self._staged_ts
                self._staged_rows, self._staged_ts = [], []
                self._flush_rows(rows, tss, now)
        # flush is where staged depth shrinks: check the low watermark and
        # resume paused sources (their buffered payloads re-enter admission)
        self._maybe_resume()

    def _flush_rows(self, rows, tss, now) -> None:
        if self._et is not None:
            # event-time gate: late rows divert (kind="late"), the rest
            # buffer until the watermark passes them; what comes back is
            # sorted by event time, timestamped WITH event time, and (for
            # lateness > 0) grouped one delivery batch per distinct event
            # time, so the device plane sees an in-order stream with
            # arrival-permutation-invariant batch boundaries
            for g_tss, g_rows in self._et.admit(tss, rows):
                self._emit_rows(g_rows, g_tss, now)
            return
        self._emit_rows(rows, tss, now)

    def _emit_rows(self, rows, tss, now) -> None:
        cap = self.batch_size
        n = len(rows)
        tele = getattr(self.ctx, "telemetry", None)
        tracing = tele is not None and tele.on
        for start in range(0, n, cap):
            t0 = time.perf_counter_ns() if tracing else 0
            chunk_rows = rows[start:start + cap]
            chunk_ts = tss[start:start + cap]
            m = len(chunk_rows)
            pad = self._pad_cap(m)  # power-of-two lane bucket for partials
            ts_arr = np.zeros(pad, dtype=np.int64)
            ts_arr[:m] = chunk_ts
            # pad timestamps monotonically so searchsorted stays correct
            if m < pad and m > 0:
                ts_arr[m:] = chunk_ts[-1]
            cols = self.codec.rows_to_columns(chunk_rows, n_pad=pad)
            if tracing:
                h2d_t0 = time.perf_counter_ns()
                batch = EventBatch.from_numpy(ts_arr, cols, m)
                trace = tele.mint(self.definition.id, m, t0=t0)
                trace.h2d_ns = time.perf_counter_ns() - h2d_t0
                # plain instance attribute: invisible to pytree flatten, so
                # it never reaches a jitted step (EventBatch is a non-slots
                # dataclass); _deliver pops it
                batch._trace = trace
                if m > 0:
                    tele.record_lag(self.definition.id, int(chunk_ts[-1]))
            else:
                batch = EventBatch.from_numpy(ts_arr, cols, m)
            self._deliver(batch, now if now is not None else
                          self.ctx.timestamp_generator.current_time())

    def _handle_error(self, e: Exception, batch: EventBatch, now: int) -> None:
        """@OnError dispatch (reference: StreamJunction.java:371-463)."""
        action = self.on_error_action
        if action == "stream" and self.fault_junction is not None:
            # route failed events + error message into `!stream`
            for ev in batch.to_host_events(self.codec):
                self.fault_junction.send_row(ev.timestamp,
                                             tuple(ev.data) + (str(e),))
            self.fault_junction.flush(now)
            return
        if action == "store":
            store = getattr(self.ctx, "error_store", None)
            if store is not None:
                events = [(ev.timestamp, tuple(ev.data))
                          for ev in batch.to_host_events(self.codec)]
                store.save(self.ctx.name, self.definition.id, events, str(e))
                return
            logging.getLogger("siddhi_tpu").error(
                "@OnError(action='STORE') on %r but no error store configured; "
                "logging instead", self.definition.id)
        logging.getLogger("siddhi_tpu").exception(
            "error processing %r events: %s", self.definition.id, e)

    def _divert_breaker(self, br, batch: EventBatch, now: int,
                        err: Optional[Exception]) -> None:
        """Route a failed/blocked query's input batch to the fault stream or
        ErrorStore instead of executing it (reference intent: OnErrorAction,
        applied at query granularity). Empty batches (heartbeats) divert
        nothing — an open breaker must not spam the store with timer ticks."""
        qname = br.owner or "?"
        msg = (f"circuit breaker open for query {qname!r}" if err is None
               else f"query {qname!r} failed: {err}")
        events = batch.to_host_events(self.codec)
        if not events:
            return
        self.ctx.statistics.track_breaker_divert(qname, len(events))
        if self.fault_junction is not None:
            for ev in events:
                self.fault_junction.send_row(ev.timestamp,
                                             tuple(ev.data) + (msg,))
            self.fault_junction.flush(now)
            return
        store = getattr(self.ctx, "error_store", None)
        if store is not None:
            store.save(self.ctx.name, self.definition.id,
                       [(ev.timestamp, tuple(ev.data)) for ev in events],
                       msg, kind="breaker")
            return
        logging.getLogger("siddhi_tpu").error(
            "%s; %d event(s) dropped (no fault stream or error store)",
            msg, len(events))

    def _divert_late(self, rows: list) -> None:
        """Events older than the watermark leave through a REPLAYABLE side
        output — ErrorStore `kind="late"` entries carrying the original
        (event_ts, row) pairs so `/errors/replay` can re-admit them through
        the gate's bypass for corrected re-emission. Never silent: the late
        counters are exact by construction."""
        et = self._et
        msg = (f"late arrival on {self.definition.id!r}: event time behind "
               f"the watermark (allowed.lateness="
               f"{et.cfg.lateness_ms if et is not None else 0} ms)")
        self.ctx.statistics.track_late(self.definition.id, len(rows))
        tele = getattr(self.ctx, "telemetry", None)
        if tele is not None:
            tele.record_late(self.definition.id, len(rows))
        store = getattr(self.ctx, "error_store", None)
        if store is not None:
            store.save(self.ctx.name, self.definition.id,
                       [(ts, tuple(row)) for ts, row in rows], msg,
                       kind="late")
            return
        logging.getLogger("siddhi_tpu").warning(
            "%s; %d row(s) dropped (no error store to divert to)",
            msg, len(rows))

    def attach_event_time(self, cfg) -> None:
        """App runtime hook: install the @app:eventTime gate (build time,
        before start_async, so the pipeline gate below sees it)."""
        from .event_time import EventTimeGate
        self._et = EventTimeGate(self, cfg)

    def release_event_time(self, now: Optional[int] = None) -> None:
        """Drain the event-time gate: staged rows pass the gate first, then
        the watermark jumps to max_ts and every buffered row delivers in
        event-time order (end-of-stream / shutdown / explicit drain)."""
        if self._et is None:
            return
        with self.ctx.controller_lock:
            self.flush(now)
            for g_tss, g_rows in self._et.release_all():
                self._emit_rows(g_rows, g_tss, now)

    def heartbeat(self, now: int) -> None:
        """Advance time with no data: flush staged rows then deliver an empty
        batch so time-window expirations fire (the watermark analogue of the
        reference's Scheduler TIMER events, core/util/Scheduler.java:48)."""
        with self.ctx.controller_lock:
            self.flush(now)
            if self._et is not None:
                # idle.timeout elapsed with rows still held: release them —
                # an idle stream must not pin its panes open forever
                for g_tss, g_rows in self._et.maybe_idle():
                    self._emit_rows(g_rows, g_tss, now)
            # timer batches carry no rows: the smallest lane bucket keeps
            # idle heartbeats off the full-capacity kernel
            empty = EventBatch.empty(self.definition, self._pad_cap(0))
            self._deliver(empty, now)

    def _deliver(self, batch: EventBatch, now: int) -> None:
        note_blocking("device.dispatch", allow=("app.controller",))
        self._reentry.flushing = True
        tele = getattr(self.ctx, "telemetry", None)
        trace = None
        if tele is not None and tele.on:
            # adopt the trace minted at batch formation; derived-stream
            # publishes and heartbeats mint one here (size unknown without a
            # device sync — left None)
            trace = batch.__dict__.pop("_trace", None)
            if trace is None:
                trace = tele.mint(self.definition.id)
            trace.deliver_t0 = time.perf_counter_ns()
            tele.push_active(trace)
        try:
            n = int(batch.count()) if self.ctx.statistics.enabled else 0
            self.ctx.statistics.track_in(self.definition.id, n)
            self.ctx.statistics.track_batch(self.definition.id)
            decoder = self.ctx.decoder
            for r in self.receivers:
                br = (getattr(r, "breaker", None)
                      or getattr(getattr(r, "runtime", None), "breaker", None))
                if br is not None and not br.allow():
                    # OPEN breaker inside its cooldown: divert without
                    # dispatching — the poisoned query stops seeing traffic,
                    # siblings on this junction keep running
                    self._divert_breaker(br, batch, now, None)
                    continue
                try:
                    if decoder is not None and isinstance(
                            r, (StreamCallback, BatchStreamCallback)):
                        decoder.submit(r, batch, now, junction=self)
                    else:
                        r.on_batch(batch, now)
                    if br is not None:
                        br.record_success()
                except Exception as e:  # noqa: BLE001
                    if br is not None:
                        # breaker-guarded receivers never kill the app: the
                        # failure counts toward the trip and the failed
                        # batch leaves through the divert path
                        qname = br.owner or getattr(r, "name", "?")
                        self.ctx.statistics.track_breaker_failure(qname)
                        if br.record_failure():
                            self.ctx.statistics.track_breaker_open(qname)
                            rec = getattr(self.ctx, "recorder", None)
                            if rec is not None:
                                # freeze evidence at the trip, not later: the
                                # rings still hold the failing batches
                                rec.trigger(
                                    "breaker_open",
                                    reason=f"query {qname!r}: {e}")
                        self._divert_breaker(br, batch, now, e)
                    elif self.on_error is not None:
                        self.on_error(e, batch)
                    elif self.on_error_action is not None:
                        self._handle_error(e, batch, now)
                    else:
                        raise
        finally:
            self._reentry.flushing = False
            if trace is not None:
                tele.pop_active(trace)
        # deliver rows staged re-entrantly during callbacks
        if self._staged_rows and len(self._staged_rows) >= self.batch_size:
            self.flush()


class InputHandler:
    """User ingestion facade (reference: core/stream/input/InputHandler.java:28).
    send() stages rows; delivery happens on batch-full or runtime.flush()."""

    def __init__(self, junction: StreamJunction) -> None:
        self.junction = junction

    def send(self, data, timestamp: Optional[int] = None) -> None:
        if isinstance(data, Event):
            self.junction.send_row(data.timestamp, data.data)
            return
        if isinstance(data, (list,)) and data and isinstance(data[0], Event):
            for ev in data:
                self.junction.send_row(ev.timestamp, ev.data)
            return
        ts = timestamp if timestamp is not None else \
            self.junction.ctx.timestamp_generator.current_time()
        self.junction.send_row(ts, tuple(data))

    def send_batch(self, rows: Sequence[Sequence],
                   timestamps=None) -> None:
        """Batched ingestion: stage many rows in ONE call (reference parity:
        InputHandler.java:50 send(Event[]) — the reference's batch overload;
        here it is also the fast path, amortizing per-event Python overhead).
        `timestamps`: None (one arrival time for the whole batch), a single
        int, or a per-row sequence."""
        n = len(rows)
        if n == 0:
            return
        if timestamps is None or isinstance(timestamps, int):
            ts = timestamps if timestamps is not None else \
                self.junction.ctx.timestamp_generator.current_time()
            tss = [ts] * n
        else:
            if len(timestamps) != n:
                raise ValueError(
                    f"send_batch: {n} rows but {len(timestamps)} timestamps")
            tss = [int(t) for t in timestamps]
        self.junction.send_rows(tss, rows)

    def send_columns(self, columns: dict, timestamps=None,
                     count: Optional[int] = None) -> None:
        """Columnar ingestion — the TPU-native public fast path: numpy
        arrays (one per attribute) encode vectorized (string columns intern
        per DISTINCT value; numeric columns cast whole-array) and enter the
        pipeline with zero per-row Python work. String columns accept str
        object arrays or pre-encoded int32 codes."""
        # resolve a blue-green redirect BEFORE any WAL/codec use: journaling
        # or interning through the v1 junction would strand records in a
        # retired journal / string table
        j = self.junction._resolve_redirect()
        n = count if count is not None else \
            min(len(v) for v in columns.values())
        if n == 0:
            return
        if timestamps is None or isinstance(timestamps, int):
            ts = timestamps if timestamps is not None else \
                j.ctx.timestamp_generator.current_time()
            ts_arr = np.full(n, ts, dtype=np.int64)
        else:
            ts_arr = np.asarray(timestamps, dtype=np.int64)
            if ts_arr.shape[0] < n:
                raise ValueError(
                    f"send_columns: {n} rows but {ts_arr.shape[0]} timestamps")
        if j.taps or j._et is not None:
            # multi-stream sequences consume rows in send order, and the
            # event-time gate classifies/reorders host rows BEFORE batch
            # formation: both fall back to the row path with the ORIGINAL
            # (un-encoded) values, in declaration order with OBJECT attrs
            lists = []
            for a in j.definition.attributes:
                if a.name in columns:
                    lists.append(list(np.asarray(columns[a.name])[:n]))
                else:
                    lists.append([None] * n)
            for ts, row in zip(ts_arr[:n].tolist(), zip(*lists)):
                j.send_row(ts, row)
            return
        if (j._pipeline is not None and j.wal is None
                and not j._lock_owned()):
            # parallel ingress: claim ring slots here, encode + intern in
            # the worker pool, device transfer double-buffered by the
            # feeder — this producer thread returns as soon as the runs
            # are claimed
            j.ctx.timestamp_generator.observe_event_time(
                int(ts_arr[:n].max()))
            done = j._pipeline.submit_columns(ts_arr, columns, n)
            if done >= n:
                return
            ts_arr = ts_arr[done:]
            columns = {k: np.asarray(v)[done:] for k, v in columns.items()}
            n -= done
        # interning mutates the app-global StringTable: hold the controller
        # lock (RLock — send_column_batch re-enters it) so the Python-loop
        # fallback cannot race the async feeder's locked encode path
        with j.ctx.controller_lock:
            # a cutover completing while we waited on the lock re-points
            # the junction: re-resolve, and nest the LIVE junction's lock
            # (re-entrant no-op when unchanged; v1->v2 ordering matches the
            # upgrade path) so journal+encode hit the live one safely
            j = j._resolve_redirect()
            with j.ctx.controller_lock:
                if j.wal is not None:
                    # inside the lock (atomic vs persist's snapshot+rotate —
                    # see send_row), journaling the ORIGINAL pre-interning
                    # values: dictionary codes are process-local and would
                    # not survive a restart
                    j.wal.append_columns(
                        j.definition.id, ts_arr[:n].tolist(),
                        {k: np.asarray(v)[:n] for k, v in columns.items()})
                cols = j.codec.encode_columns(columns, n)
                j.send_column_batch(ts_arr, cols, n)
