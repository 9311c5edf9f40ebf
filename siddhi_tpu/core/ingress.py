"""Zero-copy parallel ingress pipeline (the host half of the perf story).

Where a step program is cheap (a stateless filter's takes a fraction of a
millisecond a batch), the host's decode, interning and upload set the pace,
not the TPU (PERF.md §5 has the split per cell). The pipeline here narrows
that gap by overlapping the three host stages that the synchronous path runs
strictly in sequence:

    producers ──claim──▶ [decode/intern worker pool] ──publish──▶
        lock-free columnar ring ──pop──▶ [feeder] ──device_put──▶
            double-buffered EventBatch ──deliver──▶ engine compute

  stage 1  submit: producer threads CAS-claim contiguous ring runs
           (claim order is a total order — it IS delivery order) and hand
           the raw payload to the worker pool. Claiming is the only
           producer-side work; a full ring is blocking backpressure.
  stage 2  decode/intern: N workers convert rows/columns to fixed-width
           native buffers and write them into their pre-claimed slots with
           the GIL released (columnar.c colring_write is a plain memcpy).
           String interning is the one stage that must be deterministic —
           dictionary codes are assigned by first appearance — so workers
           take an "intern ticket" and intern in claim order; numeric
           conversion runs unordered.
  stage 3  feed: a single consumer pops contiguous published runs,
           assembles batch_size chunks, and starts the host→device
           transfer for chunk k+1 (EventBatch.from_numpy = device_put)
           BEFORE delivering chunk k under the controller lock, so H2D
           overlaps engine compute (double buffering). Chunk k is held
           only while the ring has rows for chunk k+1: the moment the
           feeder would starve, it delivers it.

Determinism/parity: with a single producer the delivered batches are
bit-identical to the synchronous path — same chunk boundaries (batch_size
from offset 0), same padding (monotone ts, zero columns, _pad_cap buckets),
same string codes (ticket-ordered interning). With multiple producers the
interleaving is the claim order, and conservation (sent == delivered +
dropped) is the invariant; tests/test_ingress_parity.py asserts both.

Gating: the pipeline is opt-in via @Async(workers='N') or
SIDDHI_INGRESS_WORKERS, and only engages when the junction has no WAL
(durability serializes through the controller lock by design), no sequence
taps (they need true per-row send order on the producer thread), a 'block'
overflow policy (drop/fault accounting lives in the bounded path), and no
OBJECT attributes (no columnar layout). Everything else falls back to the
existing MPSC ring or synchronous staging untouched.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from contextlib import contextmanager
from typing import Optional, Sequence

import numpy as np

from ..errors import SiddhiAppRuntimeError
from ..telemetry.tracing import Span, StageCells
from ..util.locks import named_condition, named_lock, note_blocking

_log = logging.getLogger("siddhi_tpu")

#: np dtype name -> colring type code (widths: b=1, i=4, l=8, f=4, d=8)
_NP_TYPECODE = {"bool": "b", "int8": "b", "int32": "i", "int64": "l",
                "float32": "f", "float64": "d"}


def _typecodes(np_dtypes: Sequence[np.dtype]) -> Optional[bytes]:
    codes = []
    for dt in np_dtypes:
        c = _NP_TYPECODE.get(dt.name)
        if c is None:
            return None
        codes.append(c)
    return "".join(codes).encode("ascii")


class _NativeColRing:
    """Thin adapter over the columnar.c lock-free ring."""

    def __init__(self, cap: int, typecodes: bytes, nmod) -> None:
        self._n = nmod
        self._r = nmod.colring_new(cap, typecodes)
        self.capacity = nmod.colring_capacity(self._r)

    def claim(self, n: int) -> int:
        return self._n.colring_claim(self._r, n)

    def write(self, start: int, n: int, ts, cols) -> None:
        self._n.colring_write(self._r, start, n, ts, cols)

    def pop(self, max_n: int, ts_out, cols_out) -> int:
        return self._n.colring_pop(self._r, max_n, ts_out, cols_out)

    def size(self) -> int:
        return self._n.colring_size(self._r)

    def hwm(self) -> int:
        return self._n.colring_hwm(self._r)


class _PyColRing:
    """Pure-Python fallback with the same surface: a lock guards claim()
    (the CAS), numpy slice copies do write/pop, and per-slot sequence
    stamps carry the publish ordering exactly like the C ring. Correctness
    twin for environments without a C toolchain — and the reference the
    parity test runs against."""

    def __init__(self, cap: int, dtypes_list: Sequence[np.dtype]) -> None:
        c = 1
        while c < cap:
            c <<= 1
        self.capacity = c
        self._mask = c - 1
        self._ts = np.zeros(c, dtype=np.int64)
        self._cols = [np.zeros(c, dtype=dt) for dt in dtypes_list]
        self._seq = np.zeros(c, dtype=np.int64)
        self._head = 0
        self._tail = 0
        self._hwm = 0
        self._lock = named_lock("ingress.pyring")

    def claim(self, n: int) -> int:
        with self._lock:
            if self._head + n - self._tail > self.capacity:
                return -1
            s = self._head
            self._head += n
            depth = self._head - self._tail
            if depth > self._hwm:
                self._hwm = depth
            return s

    def write(self, start: int, n: int, ts, cols) -> None:
        cap, mask = self.capacity, self._mask
        s0 = start & mask
        first = min(cap - s0, n)
        second = n - first
        self._ts[s0:s0 + first] = ts[:first]
        if second:
            self._ts[:second] = ts[first:n]
        for dst, src in zip(self._cols, cols):
            dst[s0:s0 + first] = src[:first]
            if second:
                dst[:second] = src[first:n]
        idx = np.arange(start, start + n) & mask
        self._seq[idx] = np.arange(start + 1, start + n + 1)

    def pop(self, max_n: int, ts_out, cols_out) -> int:
        t, cap, mask = self._tail, self.capacity, self._mask
        max_n = min(max_n, len(ts_out))
        if max_n <= 0:
            return 0
        want = np.arange(t + 1, t + max_n + 1)
        got = self._seq[np.arange(t, t + max_n) & mask]
        ok = got == want
        n = max_n if ok.all() else int(ok.argmin())
        if n == 0:
            return 0
        s0 = t & mask
        first = min(cap - s0, n)
        second = n - first
        ts_out[:first] = self._ts[s0:s0 + first]
        if second:
            ts_out[first:n] = self._ts[:second]
        for dst, src in zip(cols_out, self._cols):
            dst[:first] = src[s0:s0 + first]
            if second:
                dst[first:n] = src[:second]
        self._seq[np.arange(t, t + n) & mask] = 0
        self._tail = t + n
        return n

    def size(self) -> int:
        return self._head - self._tail

    def hwm(self) -> int:
        return self._hwm


class IngressPipeline:
    """Per-junction parallel ingress: worker pool + columnar ring + feeder.

    Thread/lock discipline (the deadlock audit):
      - producers take only the submit lock (claim+enqueue ordering) and
        never the controller lock;
      - workers take the intern ticket and, while interning, the controller
        lock (interning mutates the app-global StringTable, which
        synchronous paths mutate under that lock) — never the submit lock;
      - the feeder takes the controller lock only around delivery;
      - drain() is called only by threads NOT holding the controller lock
        (junction.flush guards on _lock_owned), so the feeder can always
        acquire it to make progress.
    """

    def __init__(self, junction, workers: int) -> None:
        from .. import native as native_mod

        self.j = junction
        self.ctx = junction.ctx
        self.workers = max(1, int(workers))
        defn = junction.definition
        if junction.codec.object_attrs:
            raise ValueError("ingress pipeline: OBJECT attrs have no "
                             "columnar layout")
        self.attrs = [a.name for a in defn.attributes]
        self.np_dtypes = [junction.codec.np_dtypes[n] for n in self.attrs]
        tcs = _typecodes(self.np_dtypes)
        if tcs is None:
            raise ValueError("ingress pipeline: unsupported dtype in schema")
        self._string_attrs = set(junction.codec.string_tables)
        self._ordered = bool(self._string_attrs)
        cap = junction._ring_cap
        if native_mod.native is not None and \
                hasattr(native_mod.native, "colring_new"):
            self.ring = _NativeColRing(cap, tcs, native_mod.native)
        else:
            self.ring = _PyColRing(cap, self.np_dtypes)
        self._q: queue.Queue = queue.Queue()
        #: claim+enqueue run under this lock so queue order == claim order —
        #: the invariant the intern tickets (and 1-worker liveness) need
        self._submit_lock = named_lock("ingress.submit")
        self._ticket_cv = named_condition("ingress.ticket")
        self._next_ticket = 0
        self._stopping = False
        self._threads: list[threading.Thread] = []
        self._feeder: Optional[threading.Thread] = None
        self._feeder_stop = threading.Event()
        self._flush_req = threading.Event()
        # a BARRIER flush (drain/stop: everything submitted must deliver)
        # as opposed to the producer-backpressure flush _claim_blocking
        # raises while the ring is full. Only a barrier may disassemble a
        # staged superstep stack: under backpressure the staging itself
        # keeps popping the ring, so space frees without flushing — and
        # at steady state backpressure is the NORMAL state, so honoring
        # it would stop supersteps from ever reaching K staged chunks.
        self._barrier_req = threading.Event()
        self._feeder_idle = threading.Event()
        self._feeder_idle.set()
        # device-resident supersteps (@app:superstep(k=) / SIDDHI_SUPERSTEP_K,
        # core/superstep.py): the feeder stages K full chunks and runs the
        # eligible query chain as one lax.scan dispatch. Built lazily at the
        # first staged superstep; a decline is logged once and recorded here
        # (statistics_report surfaces it), then the K=1 path runs forever.
        self._ss_k = 1
        self._ss_runner = None
        self._ss_decline: Optional[str] = None
        self._ss_supersteps = 0  # feeder only: dispatched supersteps
        self._ss_scan_ns = 0    # feeder only: lax.scan + device_get wall
        self._ss_replay_ns = 0  # feeder only: host replay/distribution wall
        # --- statistics (each slot has a single writer thread) ---
        self._t0 = time.monotonic()
        self._worker_busy_ns = [0] * self.workers
        self._worker_runs = [0] * self.workers
        # values the workers passed to the extension's interning, and of
        # them those its byte-keyed table resolved (StringTable.intern_array)
        self._intern_values = [0] * self.workers
        self._intern_table_hits = [0] * self.workers
        self._batches = 0       # feeder only
        self._overlapped = 0    # feeder only
        self._on_starve = 0     # feeder only
        self._rows_in = 0       # under submit lock
        self._runs_in = 0       # under submit lock
        self._frames_in = 0     # wire path, under submit lock
        self._wire_native_frames = 0  # of them, decoded by the extension
        # stage_ms: where each thread's time goes, wait told from work.
        # Units: wire per frame; claim_wait, decode, intern, ticket_wait and
        # intern_lock_wait per worker run; the rest per delivered batch.
        # wire and claim_wait come from HTTP handler threads (one per
        # connection) and are booked under the submit lock; every other
        # stage by the one long-lived thread that runs it.
        self.cells = StageCells(
            ("wire", "claim_wait", "decode", "ticket_wait",
             "intern_lock_wait", "intern", "fill", "h2d", "hold",
             "lock_wait", "dispatch", "device"),
            cpu=("wire", "intern", "h2d", "dispatch"))

    # ------------------------------------------------------------- lifecycle

    def start(self) -> None:
        sid = self.j.definition.id
        for i in range(self.workers):
            t = threading.Thread(target=self._worker_loop, args=(i,),
                                 daemon=True,
                                 name=f"siddhi-ingress-{sid}-w{i}")
            t.start()
            self._threads.append(t)
        feeder = threading.Thread(target=self._feed_loop, daemon=True,
                                  name=f"siddhi-ingress-{sid}-feed")
        feeder.start()
        self._feeder = feeder  # published started: liveness checks read it

    def stop(self) -> None:
        """Orderly shutdown: no new submits, queued runs finish (every
        claimed slot publishes — an unpublished hole would strand the rows
        behind it), the feeder delivers the remainder, threads join."""
        self._stopping = True
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join(timeout=120)
        self._barrier_req.set()
        self._flush_req.set()
        self._feeder_stop.set()
        if self._feeder is not None:
            self._feeder.join(timeout=120)
            if self._feeder.is_alive():  # pragma: no cover — wedged device
                _log.warning("ingress feeder for %r did not stop",
                             self.j.definition.id)

    # ---------------------------------------------------------------- submit

    def _claim_blocking(self, n: int,
                        deadline: Optional[float]) -> int:
        """Claim n contiguous slots, blocking while the ring is full (the
        Disruptor blocking wait strategy — a full ring IS backpressure).
        Returns -1 on block.timeout expiry, -2 when the pipeline stopped."""
        ring = self.ring
        while True:
            if self._stopping:
                return -2
            s = ring.claim(n)
            if s >= 0:
                return s
            if deadline is not None and time.monotonic() >= deadline:
                return -1
            if self._feeder is not None and not self._feeder.is_alive():
                # nothing will ever free a slot: fail the producer instead
                # of spinning (a step that raised with no @OnError handler
                # takes the feeder thread down with it)
                raise SiddhiAppRuntimeError(
                    f"ingress feeder for {self.j.definition.id!r} died; "
                    "the ring is full and will not drain")
            self._flush_req.set()
            note_blocking("ring.claim_wait", allow=("ingress.submit",))
            time.sleep(0.0002)  # noqa: SL404 — blocking claim IS the backpressure

    @contextmanager
    def _claimed(self, n: int, deadline: Optional[float], frame=None):
        """The submit lock with n slots claimed under it (see
        _claim_blocking for the value). `claim_wait` is the producer's wait
        for both: another producer holds the lock, or the ring is full.
        `frame` is the wire decode's span of the frame this run opens."""
        wait = Span("siddhi.ingress.claim_wait").begin()
        with self._submit_lock:
            try:
                s = self._claim_blocking(n, deadline)
            finally:
                wait.end()
                self.cells.book_shared("claim_wait", wait.wall_ns)
            if frame is not None:
                self.cells.book_shared("wire", frame.wall_ns, frame.cpu_ns)
            yield s

    def _deadline(self) -> Optional[float]:
        bt = self.j.block_timeout_s
        return None if bt is None else time.monotonic() + bt

    def submit_rows(self, tss: Sequence[int], rows: Sequence) -> int:
        """Producer-thread entry for the row path. Chunks into runs of at
        most batch_size, claims each, and hands (start, rows) to the
        workers. Returns the number of rows CONSUMED (claimed or shed): a
        short count means the pipeline is stopping and the caller owns the
        remainder (fall back to synchronous staging)."""
        if self._stopping or self.j._redirect is not None:
            # redirected junction (blue-green cutover): the caller's
            # synchronous fallback forwards the rows to the live junction
            return 0
        bs = self.j.batch_size
        n = len(rows)
        i = 0
        deadline = self._deadline()
        while i < n:
            m = min(bs, n - i)
            with self._claimed(m, deadline) as s:
                if s == -2:
                    return i  # claimed prefix is in flight; caller owns rest
                if s == -1:
                    self.ctx.statistics.track_ingress_drop(
                        self.j.definition.id, "block.timeout", n - i)
                    return n  # shed per block.timeout: consumed by policy
                self._rows_in += m
                self._runs_in += 1
                self._q.put(  # noqa: SL404 — unbounded queue, never blocks
                    ("rows", s, m, tss[i:i + m], rows[i:i + m]))
            i += m
        return n

    def submit_columns(self, ts_arr: np.ndarray, columns: dict,
                       n: int, frame: Optional[Span] = None,
                       frame_native: bool = False) -> int:
        """Producer-thread entry for the columnar/wire path. `columns` maps
        attr -> numpy array (numeric, pre-encoded int codes, or str/None
        objects) or, for wire frames, attr -> ('dict', strings, idx) where
        idx is int32 with -1 = null — the zero-copy dictionary form; `frame`
        is then the span of the frame's decode (io/wire.py), booked here as
        `wire`, and `frame_native` says the extension decoded its
        dictionaries (`wire_native_frames`). Returns rows consumed; see
        submit_rows."""
        if self._stopping or self.j._redirect is not None:
            return 0
        specs = []
        for name in self.attrs:
            if name not in columns:
                raise ValueError(
                    f"send_columns: missing column {name!r} for stream "
                    f"{self.j.definition.id!r}")
            src = columns[name]
            if isinstance(src, tuple) and len(src) == 3 and src[0] == "dict":
                specs.append(src)
                continue
            arr = np.asarray(src)
            if arr.shape[0] < n:
                raise ValueError(
                    f"send_columns: column {name!r} has {arr.shape[0]} "
                    f"rows, expected {n}")
            if name in self._string_attrs and \
                    not np.issubdtype(arr.dtype, np.integer):
                specs.append(("strs", arr, None))
            else:
                specs.append(("num", arr, None))
        ts_arr = np.asarray(ts_arr, dtype=np.int64)
        bs = self.j.batch_size
        i = 0
        deadline = self._deadline()
        while i < n:
            m = min(bs, n - i)
            run = []
            for kind, a, b in specs:
                if kind == "dict":
                    run.append(("dict", a, b[i:i + m]))
                else:
                    run.append((kind, a[i:i + m], None))
            with self._claimed(m, deadline, frame if i == 0 else None) as s:
                if s == -2:
                    return i
                if s == -1:
                    self.ctx.statistics.track_ingress_drop(
                        self.j.definition.id, "block.timeout", n - i)
                    return n
                self._rows_in += m
                self._runs_in += 1
                if frame is not None:
                    self._frames_in += 1
                    self._wire_native_frames += frame_native
                self._q.put(  # noqa: SL404 — unbounded queue, never blocks
                    ("cols", s, m, ts_arr[i:i + m], run))
            i += m
        return n

    # --------------------------------------------------------------- workers

    def _take_ticket(self, start: int) -> None:
        with self.cells.span("ticket_wait", "siddhi.ingress.ticket_wait"), \
                self._ticket_cv:
            while self._next_ticket != start:
                self._ticket_cv.wait(timeout=0.05)

    def _release_ticket(self, start: int, n: int) -> None:
        with self._ticket_cv:
            self._next_ticket = start + n
            self._ticket_cv.notify_all()

    @contextmanager
    def _controller_locked(self, stage: str, label: str):
        """The controller lock, the wait for it booked to `stage`."""
        lock = self.ctx.controller_lock
        with self.cells.span(stage, label):
            lock.acquire()
        try:
            yield
        finally:
            lock.release()

    def _worker_loop(self, wid: int) -> None:
        codec = self.j.codec
        dtypes_list = self.np_dtypes
        attrs = self.attrs
        string_attrs = self._string_attrs
        ordered = self._ordered
        cells = self.cells

        def interning():
            # `intern` is booked once a run, below: a run may intern for
            # several columns
            return Span("siddhi.ingress.intern", cpu=True)

        def intern_locked():
            return self._controller_locked(
                "intern_lock_wait", "siddhi.ingress.intern_lock_wait")

        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            t0 = time.perf_counter_ns()
            try:
                kind, start, m, ts, payload = item
                intern_ns = intern_cpu = n_values = n_hits = 0
                if kind == "rows":
                    if ordered:
                        # rows_to_columns interns inline (native
                        # encode_rows is one call): ticket-order the whole
                        # decode, under the controller lock because the
                        # StringTable is also mutated by synchronous paths
                        # that hold it
                        self._take_ticket(start)
                        try:
                            with interning() as span, intern_locked():
                                cols_d = codec.rows_to_columns(payload,
                                                               n_pad=m)
                        finally:
                            self._release_ticket(start, m)
                        intern_ns, intern_cpu = span.wall_ns, span.cpu_ns
                        cols = tuple(cols_d[a] for a in attrs)
                    else:
                        cols_d = codec.rows_to_columns(payload, n_pad=m)
                        cols = tuple(cols_d[a] for a in attrs)
                    ts_buf = np.asarray(ts, dtype=np.int64)
                else:  # "cols"
                    out = []
                    took = False
                    try:
                        for name, dt, (ck, a, b) in zip(attrs, dtypes_list,
                                                        payload):
                            if ck == "num":
                                out.append(np.ascontiguousarray(a, dtype=dt))
                            elif ck == "strs":
                                if not took and ordered:
                                    self._take_ticket(start)
                                    took = True
                                tbl = codec.string_tables[name]
                                with interning() as span, intern_locked():
                                    codes, nv, nh = tbl.intern_array(a)
                                n_values += nv
                                n_hits += nh
                                intern_ns += span.wall_ns
                                intern_cpu += span.cpu_ns
                                out.append(np.ascontiguousarray(
                                    codes, dtype=dt))
                            else:  # "dict": intern DISTINCT values, take
                                if not took and ordered:
                                    self._take_ticket(start)
                                    took = True
                                tbl = codec.string_tables[name]
                                with interning() as span:
                                    with intern_locked():
                                        codes, nv, nh = tbl.intern_array(a)
                                    n_values += nv
                                    n_hits += nh
                                    # idx -1 = null -> code 0 via a shifted
                                    # LUT
                                    lut = np.empty(len(codes) + 1,
                                                   dtype=np.int32)
                                    lut[0] = 0
                                    lut[1:] = codes
                                    out.append(np.ascontiguousarray(
                                        lut[b.astype(np.int64) + 1],
                                        dtype=dt))
                                intern_ns += span.wall_ns
                                intern_cpu += span.cpu_ns
                    finally:
                        if ordered:
                            if not took:
                                self._take_ticket(start)
                            self._release_ticket(start, m)
                    cols = tuple(out)
                    ts_buf = np.ascontiguousarray(ts, dtype=np.int64)
                self.ring.write(start, m, ts_buf, cols)
                spent = time.perf_counter_ns() - t0
                cells.book("intern", intern_ns, intern_cpu)
                cells.book("decode", spent - intern_ns)
                self._intern_values[wid] += n_values
                self._intern_table_hits[wid] += n_hits
                self._worker_busy_ns[wid] += spent
                self._worker_runs[wid] += 1
                self._feeder_idle.clear()
            except Exception:  # pragma: no cover — logged, slot published 0s
                _log.exception("ingress worker error on %r",
                               self.j.definition.id)
                try:
                    zero = tuple(np.zeros(m, dtype=dt)
                                 for dt in dtypes_list)
                    self.ring.write(start, m,
                                    np.zeros(m, dtype=np.int64), zero)
                except Exception:
                    pass
            finally:
                self._q.task_done()

    # ---------------------------------------------------------------- feeder

    def _upload(self, ts, cols: dict, n: int):
        """Start a chunk's host->device transfer; returns (batch, h2d ns)."""
        from .event import EventBatch
        with self.cells.span("h2d", "siddhi.feeder.h2d") as up:
            batch = EventBatch.from_numpy(ts, cols, n)
        return batch, up.wall_ns

    def _deliver_locked(self, batch, m: int, held_since: int = 0) -> None:
        """Deliver one batch under the controller lock. `device` is the
        whole of it, `lock_wait` + `dispatch`; a batch the double buffer
        held since `held_since` books that residence as `hold`."""
        j = self.j
        cells = self.cells
        t0 = time.perf_counter_ns()
        if held_since:
            cells.book("hold", t0 - held_since)
        with self._controller_locked("lock_wait", "siddhi.feeder.lock_wait"), \
                cells.span("dispatch", "siddhi.feeder.dispatch",
                           chunk=self._batches):
            if j._staged_rows or j._tap_queue:
                j.flush()  # staged (sync-path) rows first: arrival order
            j._deliver(batch, self.ctx.timestamp_generator.current_time())
        cells.book("device", time.perf_counter_ns() - t0)
        self._batches += 1

    def _superstep_dispatch(self, sstack: list) -> bool:
        """Run the staged chunks as ONE K-batch lax.scan dispatch
        (core/superstep.py). Returns False when the staged chunks must fall
        back to the per-batch path (plan declined, debugger attached,
        topology changed)."""
        if self._ss_decline is not None:
            return False
        if self._ss_runner is None or not self._ss_runner.revalidate():
            from .superstep import build_runner
            self._ss_runner, reason = build_runner(self, self._ss_k)
            if self._ss_runner is None:
                # decline LOUDLY, once — then the K=1 path runs forever
                self._ss_decline = reason
                _log.warning(
                    "superstep(k=%d) declined for stream %r: %s — "
                    "falling back to per-batch dispatch (see SL506)",
                    self._ss_k, self.j.definition.id, reason)
                return False
        try:
            dispatched = self._ss_runner.dispatch(sstack)
        except Exception as e:
            # A dispatch error must not kill the feeder thread (producers
            # would wedge in _claim_blocking forever). Disable supersteps
            # for this stream and keep running on the K=1 path. Whether the
            # staged slots were consumed depends on WHERE it failed: after
            # the scan wrote state back (superstep_committed), re-delivering
            # them through the per-batch path would double-count every
            # window and aggregate — report them consumed instead.
            committed = bool(getattr(e, "superstep_committed", False))
            self._ss_decline = f"runtime error during dispatch: {e!r}"
            self._ss_runner = None
            _log.exception(
                "superstep(k=%d) dispatch failed for stream %r "
                "(committed=%s) — disabling supersteps, falling back to "
                "per-batch dispatch", self._ss_k, self.j.definition.id,
                committed)
            return committed
        if dispatched:
            self._ss_supersteps += 1
            return True
        return False

    def _deliver_chunk(self, ts_buf, col_bufs, fill_t0: int) -> None:  # noqa: SL402 — feeder-thread only (called from _feed_loop / superstep fallback)
        """K=1 delivery of one staged full chunk (the superstep fallback
        path — identical to the inline full-chunk branch of _feed_loop)."""
        tele = getattr(self.ctx, "telemetry", None)
        tracing = tele is not None and tele.on
        bs = self.j.batch_size
        batch, h2d = self._upload(ts_buf, dict(zip(self.attrs, col_bufs)), bs)
        if tracing:
            trace = tele.mint(self.j.definition.id, bs, t0=fill_t0)
            trace.h2d_ns = h2d
            batch._trace = trace
            tele.record_lag(self.j.definition.id, int(ts_buf[bs - 1]))
        self._deliver_locked(batch, bs)

    def _feed_loop(self) -> None:
        j = self.j
        bs = j.batch_size
        ring = self.ring
        attrs = self.attrs
        tele = getattr(self.ctx, "telemetry", None)
        tracing = tele is not None and tele.on
        sid = j.definition.id
        self._ss_k = max(1, int(getattr(self.ctx, "superstep_k", 1) or 1))
        superstep = self._ss_k > 1
        sstack: list = []  # staged full chunks awaiting one K-batch dispatch
        pending = None  # the double buffer: built + transferring, undelivered
        pending_t0 = 0  # when it went in: `hold` is its residence there
        fill = 0
        fill_t0 = 0  # when the first row popped into the (empty) chunk
        ts_buf = np.zeros(bs, dtype=np.int64)
        col_bufs = [np.zeros(bs, dtype=dt) for dt in self.np_dtypes]

        def starved():
            # the feeder waiting for the workers to publish a batch's rows:
            # open from the end of one chunk's handling to the next full
            # chunk (or flush), and not while there is nothing to wait for
            return self.cells.span("fill", "siddhi.feeder.fill").begin()

        wait = starved()
        while True:
            got = ring.pop(bs - fill, ts_buf[fill:],
                           tuple(c[fill:] for c in col_bufs))
            if got:
                if fill == 0 and (tracing or superstep):
                    fill_t0 = time.perf_counter_ns()
                fill += got
            if fill == bs:
                wait.end()
                if superstep:
                    # stage the host chunk; at K staged chunks the whole
                    # stack rides one device dispatch. The staging itself
                    # is the pipelining, so the double buffer is bypassed.
                    sstack.append((ts_buf, col_bufs, fill_t0))
                    ts_buf = np.zeros(bs, dtype=np.int64)
                    col_bufs = [np.zeros(bs, dtype=dt)
                                for dt in self.np_dtypes]
                    fill = 0
                    if len(sstack) >= self._ss_k:
                        if not self._superstep_dispatch(sstack):
                            for c_ts, c_cols, c_t0 in sstack:
                                self._deliver_chunk(c_ts, c_cols, c_t0)
                        sstack = []
                        if self._ss_decline is not None:
                            superstep = False
                    wait = starved()
                    continue
                # full chunk: start its H2D NOW (from_numpy = device_put),
                # then deliver the PREVIOUS chunk, if the ring had this one's
                # rows before that one went, while this transfer runs
                batch, h2d = self._upload(ts_buf, dict(zip(attrs, col_bufs)),
                                          bs)
                if tracing:
                    trace = tele.mint(sid, bs, t0=fill_t0)
                    trace.h2d_ns = h2d
                    batch._trace = trace
                    tele.record_lag(sid, int(ts_buf[bs - 1]))
                ts_buf = np.zeros(bs, dtype=np.int64)
                col_bufs = [np.zeros(bs, dtype=dt) for dt in self.np_dtypes]
                fill = 0
                if pending is not None:
                    self._deliver_locked(pending, bs, pending_t0)
                    self._overlapped += 1
                pending = batch
                pending_t0 = time.perf_counter_ns()
                wait = starved()
                continue
            if got:
                continue  # partially filled; keep popping while data flows
            # ring momentarily empty
            flushing = self._flush_req.is_set()
            if flushing and sstack and not self._barrier_req.is_set() \
                    and not self._feeder_stop.is_set():
                # producer-backpressure flush (_claim_blocking: ring full)
                # while a superstep stack is staging: ignore it. Staging
                # keeps popping the ring, so producer space frees without
                # delivering anything — and delivering the partial fill
                # ahead of the staged chunks would reorder rows. Only a
                # drain()/stop() barrier flushes a staged stack.
                flushing = False
            if flushing and (fill or pending is not None or sstack):
                # a partial chunk is a batch too; a flush of held batches
                # alone ends no batch's wait for rows
                wait.end(units=1 if fill else 0)
                if sstack:
                    # partial superstep at a flush barrier: the staged
                    # chunks deliver per-batch (same step math, same state
                    # — bit-identical), oldest first
                    for c_ts, c_cols, c_t0 in sstack:
                        self._deliver_chunk(c_ts, c_cols, c_t0)
                    sstack = []
                if pending is not None:
                    self._deliver_locked(pending, bs, pending_t0)
                    pending = None
                if fill:
                    m = fill
                    pcap = j._pad_cap(m)
                    ts_c = np.empty(pcap, dtype=np.int64)
                    ts_c[:m] = ts_buf[:m]
                    ts_c[m:] = ts_buf[m - 1]  # monotone pad
                    cols_c = {}
                    for name, src in zip(attrs, col_bufs):
                        pad = np.zeros(pcap, dtype=src.dtype)
                        pad[:m] = src[:m]
                        cols_c[name] = pad
                    batch, h2d = self._upload(ts_c, cols_c, m)
                    if tracing:
                        trace = tele.mint(sid, m, t0=fill_t0)
                        trace.h2d_ns = h2d
                        batch._trace = trace
                        tele.record_lag(sid, int(ts_c[m - 1]))
                    fill = 0
                    ts_buf = np.zeros(bs, dtype=np.int64)
                    col_bufs = [np.zeros(bs, dtype=dt)
                                for dt in self.np_dtypes]
                    self._deliver_locked(batch, m)
                wait = starved()
                continue
            if pending is not None:
                # starved with a built batch in hand: no rows are waiting
                # whose transfer its delivery could overlap, so holding it
                # buys nothing. Only a full, already-built chunk goes early;
                # it precedes whatever is in `fill`, so order holds.
                wait.end(units=0)  # `fill` is the wait, not a dispatch
                self._deliver_locked(pending, bs, pending_t0)
                pending = None
                self._on_starve += 1
                wait = starved()
                continue
            if fill == 0 and not sstack \
                    and ring.size() == 0 and self._q.unfinished_tasks == 0:
                wait.drop()  # idle: nothing was sent, so not starved
                self._feeder_idle.set()
                if self._feeder_stop.is_set():
                    return
                self._flush_req.clear()
                self._barrier_req.clear()
                self._flush_req.wait(timeout=0.001)
                wait = starved()
            elif self._feeder_stop.is_set() and ring.size() == 0 \
                    and self._q.unfinished_tasks == 0:
                # stopping with a partial chunk: force the final flush
                self._flush_req.set()
            else:
                time.sleep(0.0002)

    # ----------------------------------------------------------------- drain

    def drain(self, timeout: float = 120.0) -> None:
        """Barrier: every row submitted before this call is delivered when
        it returns. Callers must NOT hold the controller lock (the feeder
        needs it to deliver); junction.flush() guards on _lock_owned.
        Raises SiddhiAppRuntimeError when the workers or the feeder have
        died or `timeout` seconds pass first — returning as if drained
        would let the caller read results that are not there yet."""
        deadline = time.monotonic() + timeout

        def check(stage: str) -> None:
            dead = [t.name for t in (*self._threads, self._feeder)
                    if t is not None and not t.is_alive()]
            if dead and not self._stopping:
                why = f"thread(s) {dead} died"
            elif time.monotonic() >= deadline:
                why = f"not {stage} within {timeout:.0f}s"
            else:
                return
            raise SiddhiAppRuntimeError(
                f"ingress drain on {self.j.definition.id!r}: {why} "
                f"(ring={self.ring.size()}, "
                f"queued={self._q.unfinished_tasks})")

        # every claimed run is encoded + published BEFORE the barrier goes
        # up: a flush request that meets a half-written run would deliver
        # its published prefix as a partial chunk (same rows, but a chunk
        # boundary — and a lane bucket — the synchronous path never makes)
        while self._q.unfinished_tasks:
            check("published")
            time.sleep(0.0005)
        while True:
            self._barrier_req.set()
            self._flush_req.set()
            if self._feeder_idle.is_set() and self.ring.size() == 0 \
                    and self._q.unfinished_tasks == 0:
                return
            check("delivered")
            time.sleep(0.0005)

    def size(self) -> int:
        return self.ring.size() + self._q.unfinished_tasks

    # ------------------------------------------------------------ statistics

    def stats_snapshot(self) -> dict:
        elapsed_ns = max((time.monotonic() - self._t0) * 1e9, 1.0)
        busy = sum(self._worker_busy_ns)
        delivered = self._batches
        return {
            "workers": self.workers,
            "superstep_k": self._ss_k,
            "supersteps_dispatched": self._ss_supersteps,
            "superstep_decline": self._ss_decline,
            "superstep_scan_ms": self._ss_scan_ns / 1e6,
            "superstep_replay_ms": self._ss_replay_ns / 1e6,
            "ring_capacity": self.ring.capacity,
            "ring_depth_hwm": self.ring.hwm(),
            "rows_in": self._rows_in,
            "runs_in": self._runs_in,
            "frames_in": self._frames_in,
            "wire_native_frames": self._wire_native_frames,
            "intern_values": sum(self._intern_values),
            "intern_table_hits": sum(self._intern_table_hits),
            "batches_delivered": delivered,
            # a held batch leaves behind the next one's upload (overlapped),
            # when the ring runs empty (on starve) or at a flush (the rest)
            "batches_overlapped": self._overlapped,
            "batches_delivered_on_starve": self._on_starve,
            "h2d_overlap_ratio": (self._overlapped / delivered
                                  if delivered else 0.0),
            "worker_utilization": busy / (elapsed_ns * self.workers),
            # per-stage: cumulative wall, how many units it covers, and the
            # per-unit mean — total alone made per-batch math impossible
            # (decode/intern are per worker RUN; h2d/device are per BATCH)
            "stage_ms": self.cells.snapshot(),
        }


# ==========================================================================
# partition-key shard router (parallel/shard_plane.py's ingress half)
# ==========================================================================


class ShardRouter:
    """Routes rows to shard replicas by partition-key hash BEFORE any
    interning — dictionary codes are process- (and shard-) local, so the
    hash runs over ORIGINAL values: raw UTF-8 bytes for strings, the
    int64/float-bit mixing of `parallel.sharded.np_shard_of` for numerics
    (host routing stays bit-exact with the device key hash).

    Two-level map: `slot = hash(value) % n_slots` is stable for the life of
    the app; `assignment[slot] -> shard` is the mutable part — rebalancing
    republishes the assignment table instead of rehashing the world, and
    per-slot routed-row counters feed the skew detector. Row order within
    one (producer, key) pair is preserved: a boolean-mask split keeps
    relative order, and a key maps to exactly one shard per epoch."""

    #: FNV-1a 64-bit parameters — shared with np_shard_of
    _FNV_OFFSET = 0xCBF29CE484222325
    _FNV_PRIME = 0x100000001B3
    _MASK = (1 << 64) - 1

    def __init__(self, key: str, n_shards: int, n_slots: int = 64,
                 assignment=None) -> None:
        import threading

        import numpy as np
        if n_slots < n_shards:
            n_slots = n_shards
        self.key = key
        self.n_shards = n_shards
        self.n_slots = n_slots
        if assignment is not None:
            assignment = np.asarray(assignment, dtype=np.int64)
            if assignment.shape[0] != n_slots or \
                    (len(assignment) and assignment.max() >= n_shards):
                raise ValueError(
                    f"shard assignment must map {n_slots} slots to "
                    f"[0, {n_shards})")
            self.assignment = assignment.copy()
        else:
            self.assignment = np.arange(n_slots, dtype=np.int64) % n_shards
        self._lock = named_lock("ingress.shard_router")
        #: rows routed per slot / per shard since the current epoch began
        self.slot_rows = np.zeros(n_slots, dtype=np.int64)
        self.routed = np.zeros(n_shards, dtype=np.int64)
        self.total_rows = 0
        #: string value -> slot memo (the router-side analogue of the
        #: string table: the key universe is the dictionary universe)
        self._str_slots: dict = {}

    # ------------------------------------------------------------ hashing

    def _slot_of_str(self, s: str) -> int:
        slot = self._str_slots.get(s)
        if slot is None:
            h = self._FNV_OFFSET
            for b in s.encode("utf-8"):
                h = ((h ^ b) * self._FNV_PRIME) & self._MASK
            h ^= h >> 29
            slot = (h & 0xFFFFFFFF) % self.n_slots
            self._str_slots[s] = slot
        return slot

    def slot_of(self, value) -> int:
        """Stable slot of one ORIGINAL key value (scalar mirror of
        `slots_of_column` — tests assert they agree)."""
        import struct
        if value is None:
            return 0
        if isinstance(value, str):
            return self._slot_of_str(value)
        if isinstance(value, bool):
            value = int(value)
        if isinstance(value, float):
            value = struct.unpack("<q", struct.pack("<d", value))[0]
        x = int(value) & self._MASK
        h = ((self._FNV_OFFSET ^ x) * self._FNV_PRIME) & self._MASK
        h ^= h >> 29
        return (h & 0xFFFFFFFF) % self.n_slots

    def slots_of_column(self, col, n=None):
        """Vectorized `slot_of` over one key column: a numpy array, an
        object array of strings, or a `('dict', values, idx)` wire triple
        (hashed per DISTINCT value, mapped through the index)."""
        import numpy as np
        if isinstance(col, tuple) and len(col) == 3 and col[0] == "dict":
            _tag, values, idx = col
            idx = np.asarray(idx)[:n] if n is not None else np.asarray(idx)
            vslots = np.array(
                [self.slot_of(v) for v in values], dtype=np.int64) \
                if len(values) else np.zeros(0, dtype=np.int64)
            out = np.zeros(idx.shape[0], dtype=np.int64)
            valid = idx >= 0
            if valid.any():
                out[valid] = vslots[idx[valid]]
            return out
        arr = np.asarray(col)
        if n is not None:
            arr = arr[:n]
        if arr.dtype.kind in ("O", "U"):
            return np.array([self.slot_of(v) for v in arr.tolist()],
                            dtype=np.int64)
        from ..parallel.sharded import np_shard_of
        return np_shard_of([arr], self.n_slots).astype(np.int64)

    # ------------------------------------------------------------ routing

    def shard_of(self, value) -> int:
        return int(self.assignment[self.slot_of(value)])

    def republish(self, assignment) -> None:
        """Atomically swap the slot→shard table. A rebalance (or a front
        tier refreshing its view from a newer shardmeta epoch) republishes
        the assignment instead of rehashing the world — `slot = hash(key)
        % n_slots` never changes, so in-flight `slot_of` results stay
        valid across the swap."""
        import numpy as np
        arr = np.asarray(assignment, dtype=np.int64)
        if arr.shape[0] != self.n_slots or \
                (len(arr) and arr.max() >= self.n_shards):
            raise ValueError(
                f"shard assignment must map {self.n_slots} slots to "
                f"[0, {self.n_shards})")
        with self._lock:
            self.assignment = arr.copy()

    def note_routed(self, slots) -> None:
        """Account one routed batch into the skew counters."""
        import numpy as np
        counts = np.bincount(slots, minlength=self.n_slots)
        with self._lock:
            self.slot_rows += counts
            np.add.at(self.routed, self.assignment, counts)
            self.total_rows += int(counts.sum())

    def split_rows(self, tss, rows, key_index: int):
        """{shard: (tss, rows)} preserving per-shard row order."""
        groups: dict = {}
        slots = []
        for ts, row in zip(tss, rows):
            slot = self.slot_of(row[key_index])
            slots.append(slot)
            shard = int(self.assignment[slot])
            g = groups.get(shard)
            if g is None:
                g = groups[shard] = ([], [])
            g[0].append(ts)
            g[1].append(row)
        import numpy as np
        self.note_routed(np.asarray(slots, dtype=np.int64))
        return groups

    def split_columns(self, columns: dict, ts_arr, n: int):
        """{shard: (ts_sub, cols_sub, count)} — columns may mix numpy
        arrays and `('dict', values, idx)` triples; dict columns are
        COMPACTED per shard (`io.wire.subset_dict_column`) so each shard
        interns only the values its keys reference."""
        import numpy as np

        from ..io.wire import subset_dict_column
        key_col = columns.get(self.key)
        if key_col is None:
            raise KeyError(
                f"shard routing: batch has no partition-key column "
                f"{self.key!r}")
        slots = self.slots_of_column(key_col, n)
        self.note_routed(slots)
        shards = self.assignment[slots]
        out: dict = {}
        for shard in np.unique(shards):
            sel = shards == shard
            cols_sub = {}
            for name, col in columns.items():
                if isinstance(col, tuple) and len(col) == 3 \
                        and col[0] == "dict":
                    cols_sub[name] = subset_dict_column(
                        col[1], np.asarray(col[2])[:n], sel)
                else:
                    cols_sub[name] = np.asarray(col)[:n][sel]
            out[int(shard)] = (np.asarray(ts_arr)[:n][sel], cols_sub,
                               int(sel.sum()))
        return out

    # ------------------------------------------------------- skew detector

    def skew_report(self) -> dict:
        """Per-shard routed totals + the imbalance ratio the rebalance
        trigger keys off (max shard load over the even-split ideal)."""
        import numpy as np
        with self._lock:
            routed = self.routed.copy()
            slot_rows = self.slot_rows.copy()
            total = self.total_rows
        ideal = total / self.n_shards if self.n_shards else 0.0
        imbalance = float(routed.max() / ideal) if ideal > 0 else 1.0
        hot = np.argsort(slot_rows)[::-1][:8]
        return {
            "total_rows": int(total),
            "per_shard": {f"s{i}": int(r) for i, r in enumerate(routed)},
            "imbalance": imbalance,
            "hot_slots": [
                {"slot": int(s), "shard": int(self.assignment[s]),
                 "rows": int(slot_rows[s])}
                for s in hot if slot_rows[s] > 0],
        }

    def propose_assignment(self):
        """Greedy LPT bin-packing of slots onto shards by observed load —
        heaviest slot first onto the lightest shard. Slots with no traffic
        keep their current shard (no gratuitous state moves)."""
        import numpy as np
        with self._lock:
            slot_rows = self.slot_rows.copy()
        proposal = self.assignment.copy()
        load = np.zeros(self.n_shards, dtype=np.int64)
        active = [int(s) for s in np.argsort(slot_rows)[::-1]
                  if slot_rows[s] > 0]
        for slot in active:
            shard = int(np.argmin(load))
            proposal[slot] = shard
            load[shard] += int(slot_rows[slot])
        return proposal

    def reset_counters(self) -> None:
        with self._lock:
            self.slot_rows[:] = 0
            self.routed[:] = 0
            self.total_rows = 0
