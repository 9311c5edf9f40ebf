"""Batch tracing: monotone batch IDs + per-stage spans + slow-batch ring.

A BatchTrace is minted where a micro-batch is FORMED at ingress (the
parallel pipeline's feeder, the MPSC/staging flush, the columnar path) and
rides on the EventBatch as a plain instance attribute (`batch._trace`) —
invisible to JAX's pytree flatten, so it never reaches a jitted step or
perturbs compilation. StreamJunction._deliver adopts the trace (minting one
on the fly for derived-stream publishes and heartbeats), pushes it onto a
thread-local active stack for the duration of the fan-out, and query steps
and sinks attribute their spans to the innermost active trace without any
argument threading.

Stage model (all spans in ns, recorded into per-stage histograms):

  accept   trace mint: the instant the batch's first row left the staging
           structure and batch assembly began
  stage    mint → delivery start, minus h2d (encode + ring/queue wait +
           double-buffer residence)
  h2d      EventBatch.from_numpy (host→device transfer start)
  device   sum of query/join/pattern step wall time inside the fan-out,
           EXCLUSIVE of nested sink time — sinks publish from inside the
           query's own distribution, so the raw query span contains the
           sink span; subtracting it keeps device + sink additive and lets
           the doctor attribute a slow consumer to `sink`, not `device`
  compile  the wall of step calls that traced and compiled (a shape's first
           batch). Host work, and not a `device` span: one compile in a
           short history would otherwise be that stage's p99 and outweigh
           the stage that really breaches an objective
  sink     sum of Sink.publish_rows wall time inside the fan-out, credited
           to EVERY trace on the active stack (the derived output stream's
           trace and the ingress trace it is nested under)
  e2e      mint → delivery end

Slow-batch exemplars: a bounded worst-N ring (by e2e) with the stage
breakdown, query names, and batch size — statistics_report()
["slow_batches"]. A separate recent-completion deque
(`recent_summaries()`) exists for tests asserting ID propagation; both
are O(1) per batch (summary dicts are built on read, not on the hot
path).

Stage spans (`Span`, `StageCells`): the served path's threads tell wait
from work with one primitive. A span adds its wall ns (and, where asked,
its thread's CPU ns: wall - CPU is time the thread did not run, waiting for
the interpreter lock, a lock or I/O) and one unit to a cumulative cell, and
opens a `jax.profiler.TraceAnnotation` over the same interval, so inside
any profiler session (SIDDHI_PROFILE, a benchmark's slice) it lies in
`/host:CPU` of the same xplane as the device ops, on one clock. Always on;
entered per frame, per worker run or per batch, never per row. The cells
are what `statistics_report()` shows as `ingress_pipeline.<stream>.stage_ms`
and `readback.stage_ms`.

Step stages (`STEP_STAGES`, `stage`): the device half. A step program's
body is cut into named stages with `with stage("selector"):`, which is
`jax.named_scope("siddhi.selector")` and nothing else: the name goes into
the `op_name` metadata of every HLO operation traced inside it
(`jit(step)/siddhi.selector/siddhi.selector/sort/sort`), so a profiler
session shows it on each device op and `benchmarks/stages.py` sums device
time by it. It exists at trace time only: nothing runs per batch, the
lowered module is the same module (a scope is debug location, and jax
strips that from the compile cache's key), and an executable loaded from a
cache that an unscoped build wrote carries no scope until that cache is
rebuilt (`docs/OBSERVABILITY.md`). One vocabulary for every step program,
two levels at most; an undeclared name raises where the step is traced.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from collections import deque
from typing import Optional

import jax
from jax.profiler import TraceAnnotation

from ..util.locks import named_lock
from .metrics import Histogram, MetricsRegistry, bucket_index

#: worst-N exemplar ring size
SLOW_RING = 8
#: recent-completion ring size (test/debug surface)
RECENT_RING = 64

#: every stage a step program may name; `a/b` is a part of `a`
STEP_STAGES = (
    "filter",
    "window", "window/append", "window/expire", "window/fetch",
    "window/route",  # a keyed window's slot lookup and (slot, lane) order
    "selector", "selector/sort", "selector/gather", "selector/scan",
    "selector/scatter",
    "emit",
    # the join's probe programs
    "probe", "compact", "frames",
    # the pattern's step programs
    "append", "match", "match/expire",
    "match/range",  # the key-and-threshold plan's pass over its table
    # a table's device index (core/table_index.py): the key's slot, the
    # events of a key in arrival order, the rows written
    "table", "table/lookup", "table/order", "table/write",
)
#: the top-level stages of each family of step program, in program order
STEP_FAMILIES = {
    "query": ("filter", "window", "selector", "emit"),
    "join": ("filter", "window", "table", "probe", "compact", "frames",
             "selector", "emit"),
    "pattern": ("filter", "append", "match", "frames", "selector", "emit"),
    "table": ("table",),
}
STAGE_PREFIX = "siddhi."


def stage(name: str):
    """The scope of one declared stage of a step program: a context manager
    for trace time (`jax.named_scope`), no computation."""
    if name not in STEP_STAGES:
        raise ValueError(f"undeclared step stage {name!r}: STEP_STAGES in "
                         "telemetry/tracing.py names them all")
    return jax.named_scope(STAGE_PREFIX + name)


class Span:
    """One timed interval: `with span:` or, for an interval shaped by a
    loop, `begin()` ... `end()`. After its end `wall_ns` (and `cpu_ns` with
    `cpu=True`) say how long it was; a span made by `StageCells.span` also
    books them to its cell. `label` and `ids` name the profiler event and
    its stats."""

    __slots__ = ("wall_ns", "cpu_ns", "_cell", "_cpu", "_ann", "_t0", "_c0")

    def __init__(self, label: str, cpu: bool = False, cell=None,
                 **ids) -> None:
        self._ann = TraceAnnotation(label, **ids)
        self._cpu = cpu
        self._cell = cell
        self.wall_ns = self.cpu_ns = 0

    def begin(self) -> "Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        if self._cpu:  # read inside the wall's interval: CPU <= wall
            self._c0 = time.thread_time_ns()
        return self

    def end(self, units: int = 1) -> None:
        if self._cpu:
            self.cpu_ns = time.thread_time_ns() - self._c0
        self.wall_ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(None, None, None)
        if self._cell is not None:
            _add(self._cell, self.wall_ns, units, self.cpu_ns)

    def drop(self) -> None:
        """Close the span and book nothing (the interval was not what the
        cell counts: the feeder idle, not starved)."""
        self._ann.__exit__(None, None, None)

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        self.end()


def _add(cell: list, wall_ns: int, units: int, cpu_ns: int) -> None:
    cell[0] += wall_ns
    cell[1] += units
    cell[2] += cpu_ns


class StageCells:
    """Cumulative wall ns, units and CPU ns per named stage. Every writer
    thread owns a slot (single writer, no lock on the hot path) and
    `snapshot()` sums the slots. Threads that do not live long (one HTTP
    handler per connection) must not grow a slot each: they book through
    `book_shared`, under a lock of the caller's."""

    def __init__(self, stages, cpu=()) -> None:
        self._stages = tuple(stages)
        self._cpu = frozenset(cpu)
        self._shared = self._new_slot()
        self._slots = {None: self._shared}

    def _new_slot(self) -> dict:
        return {s: [0, 0, 0] for s in self._stages}

    def _slot(self) -> dict:
        me = threading.get_ident()
        slot = self._slots.get(me)
        if slot is None:
            slot = self._slots[me] = self._new_slot()
        return slot

    def span(self, stage: str, label: str, **ids) -> Span:
        """A span of the calling thread that books to `stage` at its end."""
        return Span(label, stage in self._cpu, self._slot()[stage], **ids)

    def book(self, stage: str, wall_ns: int, cpu_ns: int = 0,
             units: int = 1) -> None:
        """`units` of `stage`, timed by the calling thread itself."""
        _add(self._slot()[stage], wall_ns, units, cpu_ns)

    def book_shared(self, stage: str, wall_ns: int, cpu_ns: int = 0) -> None:
        """`book` for short-lived threads; the caller holds the one lock
        under which this stage is booked."""
        _add(self._shared[stage], wall_ns, 1, cpu_ns)

    def snapshot(self) -> dict:
        """stage -> {total_ms, batches (units), mean_ms[, cpu_ms]}."""
        out = {}
        slots = list(self._slots.values())
        for stage in self._stages:
            wall = units = cpu = 0
            for slot in slots:
                w, n, c = slot[stage]
                wall += w
                units += n
                cpu += c
            total_ms = wall / 1e6
            out[stage] = {"total_ms": total_ms, "batches": units,
                          "mean_ms": total_ms / units if units else 0.0}
            if stage in self._cpu:
                out[stage]["cpu_ms"] = cpu / 1e6
        return out


class BatchTrace:
    __slots__ = ("batch_id", "stream", "size", "t0", "h2d_ns", "device_ns",
                 "compile_ns", "sink_ns", "deliver_t0", "queries",
                 "superstep")

    def __init__(self, batch_id: int, stream: str, size: Optional[int],
                 t0: int) -> None:
        self.batch_id = batch_id
        self.stream = stream
        self.size = size  # rows when known at mint; None for derived batches
        self.t0 = t0
        self.h2d_ns = 0
        self.device_ns = 0
        self.compile_ns = 0
        self.sink_ns = 0
        self.deliver_t0 = 0
        self.queries: list[str] = []
        #: K of the superstep this batch rode in (core/superstep.py), 0 for
        #: per-batch dispatch — the trace stays per INNER batch either way
        self.superstep = 0

    def exclusive(self) -> tuple:
        """(device ns, compile ns) exclusive of sink: sink publishes run
        nested inside query spans, so taking them out keeps the stage
        shares additive. Out of `device` first; a batch whose only step
        call compiled holds its sink span inside `compile`."""
        device = self.device_ns - self.sink_ns
        if device >= 0:
            return device, self.compile_ns
        return 0, max(self.compile_ns + device, 0)

    def summary(self, t_end: int) -> dict:
        e2e = t_end - self.t0
        stage = max(self.deliver_t0 - self.t0 - self.h2d_ns, 0)
        device, compile_ns = self.exclusive()
        out = {
            "batch_id": self.batch_id,
            "stream": self.stream,
            "batch_size": self.size,
            "queries": list(self.queries),
            "e2e_ms": e2e / 1e6,
            "stages_ms": {
                "stage": stage / 1e6,
                "h2d": self.h2d_ns / 1e6,
                "device": device / 1e6,
                "compile": compile_ns / 1e6,
                "sink": self.sink_ns / 1e6,
            },
        }
        if self.superstep:
            out["superstep_k"] = self.superstep
        return out


class AppTelemetry:
    """Per-app telemetry façade: the metrics registry and the batch tracer
    state. Attached to SiddhiAppContext.telemetry by the app runtime."""

    def __init__(self, app_name: str, enabled: Optional[bool] = None) -> None:
        from . import telemetry_enabled
        self.app = app_name
        self.on = telemetry_enabled() if enabled is None else enabled
        self.registry = MetricsRegistry()
        r = self.registry
        # always-on families, declared up front so /metrics renders them
        # (HELP/TYPE) even before the first batch
        self.batches = r.counter(
            "siddhi_batches_total",
            "Micro-batches delivered per stream junction", ("stream",))
        self.events = r.counter(
            "siddhi_events_total",
            "Rows delivered per stream (ingress batches with exact counts)",
            ("stream",))
        self.stage_hist = r.histogram(
            "siddhi_stage_latency_seconds",
            "Per-stage batch latency (stage|h2d|device|compile|sink|e2e)",
            ("stream", "stage"))
        self.query_hist = r.histogram(
            "siddhi_query_latency_seconds",
            "Per-query step wall time (device dispatch + distribute)",
            ("query",))
        self.sink_hist = r.histogram(
            "siddhi_sink_latency_seconds",
            "Sink.publish_rows wall time per output stream", ("stream",))
        self.sink_events = r.counter(
            "siddhi_sink_published_total",
            "Rows handed to Sink.publish_rows per output stream",
            ("stream",))
        self.upgrade_hist = r.histogram(
            "siddhi_upgrade_cutover_seconds",
            "Blue-green hot-swap source-paused (cutover) wall time")
        self.lag_gauge = r.gauge(
            "siddhi_event_time_lag_seconds",
            "Event-time lag at delivery: wall clock minus the newest "
            "external row timestamp in the batch (epoch-ms producers only; "
            "also re-sampled at every watermark advance so idle streams "
            "don't freeze)",
            ("stream",))
        self.wm_gauge = r.gauge(
            "siddhi_watermark_lag_seconds",
            "Watermark lag: wall clock minus the stream's event-time "
            "watermark (max event ts minus allowed.lateness; epoch-ms "
            "producers only)",
            ("stream",))
        self.late_counter = r.counter(
            "siddhi_late_events_total",
            "Rows older than the event-time watermark diverted to the "
            "ErrorStore (kind=\"late\") per stream", ("stream",))
        self.tenant_ms = r.counter(
            "siddhi_tenant_device_ms_total",
            "Metered device milliseconds per tenant (equal-share "
            "attribution inside fused groups)", ("tenant",))
        self.tenant_queries = r.gauge(
            "siddhi_tenant_queries",
            "Attached queries per tenant", ("tenant",))
        self.splices = r.counter(
            "siddhi_splices_total",
            "One-retrace query splices by kind (in|out|declined|failed)",
            ("kind",))
        self.splice_ms = r.gauge(
            "siddhi_splice_retrace_ms",
            "Last successful splice's retrace+compile wall milliseconds")
        # tracer state
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._slow: list[tuple[float, int, dict]] = []  # (e2e_ms, id, summary)
        self._slow_floor = 0.0  # cheapest e2e_ms in a full ring (fast reject)
        self._slow_lock = named_lock("telemetry.trace.slow")
        self.recent: deque = deque(maxlen=RECENT_RING)  # (trace, t_end_ns)
        # per-series child caches: Family.labels() is a guarded dict walk,
        # and pop_active touches seven series per delivery — resolving them
        # once per stream keeps the always-on path in single-dict-get
        # territory (racing first lookups are safe: labels() is idempotent)
        self._stream_cells: dict = {}
        self._query_cells: dict = {}
        self._sink_cells: dict = {}
        self._lag_cells: dict = {}
        self._wm_cells: dict = {}
        self._late_cells: dict = {}

    # ---------------------------------------------------------------- tracing

    def mint(self, stream: str, size: Optional[int] = None,
             t0: Optional[int] = None) -> BatchTrace:
        return BatchTrace(next(self._ids), stream, size,
                          time.perf_counter_ns() if t0 is None else t0)

    def push_active(self, trace: BatchTrace) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(trace)

    def active(self) -> Optional[BatchTrace]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def pop_active(self, trace: BatchTrace) -> None:
        """Close the delivery: record every stage span + counters, then
        retire the trace into the recent/slow rings."""
        stack = self._tls.stack
        stack.pop()
        t_end = time.perf_counter_ns()
        stream = trace.stream
        cells = self._stream_cells.get(stream)
        if cells is None:
            sh = self.stage_hist
            cells = (self.batches.labels(stream),
                     self.events.labels(stream),
                     sh.labels(stream, "stage"), sh.labels(stream, "h2d"),
                     sh.labels(stream, "device"), sh.labels(stream, "sink"),
                     sh.labels(stream, "e2e"))
            self._stream_cells[stream] = cells
        batches_c, events_c, stage_c, h2d_c, device_c, sink_c, e2e_c = cells
        stage_ns = trace.deliver_t0 - trace.t0 - trace.h2d_ns
        stage_c.observe_ns(stage_ns if stage_ns > 0 else 0)
        if trace.h2d_ns:
            h2d_c.observe_ns(trace.h2d_ns)
        device_ns, compile_ns = trace.exclusive()
        if device_ns > 0:
            device_c.observe_ns(device_ns)
        if compile_ns:  # rare: its cell is resolved when it happens
            self.stage_hist.labels(stream, "compile").observe_ns(compile_ns)
        if trace.sink_ns:
            sink_c.observe_ns(trace.sink_ns)
        e2e_ns = t_end - trace.t0
        e2e_c.observe_ns(e2e_ns)
        batches_c.inc()
        if trace.size is not None:
            events_c.inc(trace.size)
        self.recent.append((trace, t_end))
        e2e_ms = e2e_ns / 1e6
        # summary dicts are built only for the worst-N ring; the common
        # (fast-batch) path does one float compare and moves on
        if len(self._slow) < SLOW_RING or e2e_ms > self._slow_floor:
            with self._slow_lock:
                if len(self._slow) < SLOW_RING:
                    heapq.heappush(
                        self._slow,
                        (e2e_ms, trace.batch_id, trace.summary(t_end)))
                elif e2e_ms > self._slow[0][0]:
                    heapq.heapreplace(
                        self._slow,
                        (e2e_ms, trace.batch_id, trace.summary(t_end)))
                if len(self._slow) >= SLOW_RING:
                    self._slow_floor = self._slow[0][0]

    # ------------------------------------------------------------ span hooks

    def record_query(self, query: str, ns: int,
                     compiled: bool = False) -> None:
        """One step call's wall; `compiled` when the call traced and
        compiled, which books it as the batch's `compile`, not `device`."""
        h = self._query_cells.get(query)
        if h is None:
            h = self._query_cells[query] = self.query_hist.labels(query)
        h.observe_ns(ns)
        tr = self.active()
        if tr is not None:
            if compiled:
                tr.compile_ns += ns
            else:
                tr.device_ns += ns
            tr.queries.append(query)

    def query_cell(self, query: str):
        """Pre-resolve the per-query histogram cell so fused groups can
        record their whole membership without N dict lookups per batch."""
        h = self._query_cells.get(query)
        if h is None:
            h = self._query_cells[query] = self.query_hist.labels(query)
        return h

    def record_query_block(self, cells, names, ns: int,
                           compiled: bool = False) -> None:
        """Bulk `record_query` for one fused group: every member reports
        the same share `ns` of the group's measured span, so the bucket
        index is computed once and the cells (from `query_cell`) are
        observed directly. Series produced are identical to calling
        `record_query(name, ns, compiled)` per member."""
        bi = bucket_index(ns)
        for h in cells:
            h.observe_ns_at(bi, ns)
        tr = self.active()
        if tr is not None:
            if compiled:
                tr.compile_ns += ns * len(names)
            else:
                tr.device_ns += ns * len(names)
            tr.queries.extend(names)

    def record_splice(self, kind: str, ms=None) -> None:
        """One splice event (kind: in|out|declined|failed) — always on,
        like the counters in statistics: a failed/declined splice is an
        operational event, not a metric."""
        self.splices.labels(kind).inc()
        if ms is not None:
            self.splice_ms.labels().set(float(ms))

    def record_lag(self, stream: str, newest_ts_ms: int) -> None:
        """Event-time lag at delivery: how stale the newest row of the
        batch already was when the engine saw it (upstream queueing the
        processing-latency stages can't see). Meaningful only when the
        producer stamps epoch milliseconds — synthetic/logical timestamps
        (tests, playback counters) are ignored via a plausibility window
        so the gauge never reports a ~50-year lag for counter timestamps."""
        if newest_ts_ms < 1_000_000_000_000:  # pre-2001 epoch-ms: synthetic
            return
        g = self._lag_cells.get(stream)
        if g is None:
            g = self._lag_cells[stream] = self.lag_gauge.labels(stream)
        g.set(max(time.time() - newest_ts_ms / 1e3, 0.0))

    def record_watermark(self, stream: str, wm_ms: int) -> None:
        """Watermark lag at advance (event-time gates, core/event_time.py).
        Same epoch-ms plausibility guard as record_lag — synthetic/logical
        clocks must not render as a ~50-year lag."""
        if wm_ms < 1_000_000_000_000:
            return
        g = self._wm_cells.get(stream)
        if g is None:
            g = self._wm_cells[stream] = self.wm_gauge.labels(stream)
        g.set(max(time.time() - wm_ms / 1e3, 0.0))

    def record_late(self, stream: str, n: int) -> None:
        """Late-diversion counter — always on (a correctness signal, like
        the sink families), independent of the batch tracer."""
        c = self._late_cells.get(stream)
        if c is None:
            c = self._late_cells[stream] = self.late_counter.labels(stream)
        c.inc(n)

    def observe_upgrade(self, pause_ms: float) -> None:
        """One committed hot-swap's cutover pause (core/upgrade.py)."""
        self.upgrade_hist.labels().observe_ns(int(pause_ms * 1e6))

    def record_sink(self, stream: str, rows: int, ns: int) -> None:
        cells = self._sink_cells.get(stream)
        if cells is None:
            cells = (self.sink_hist.labels(stream),
                     self.sink_events.labels(stream))
            self._sink_cells[stream] = cells
        cells[0].observe_ns(ns)
        cells[1].inc(rows)
        # credit the sink span to the whole active stack: the innermost
        # (derived output stream) trace owns it directly, and each outer
        # trace needs it to net sink time OUT of its enclosing query spans
        stack = getattr(self._tls, "stack", None)
        if stack:
            for tr in stack:
                tr.sink_ns += ns

    # --------------------------------------------------------------- reports

    def slow_batches(self) -> list[dict]:
        """Worst-N exemplars, slowest first."""
        with self._slow_lock:
            items = sorted(self._slow, key=lambda x: -x[0])
        return [s for _, _, s in items]

    def recent_summaries(self) -> list[dict]:
        """Summaries of the last RECENT_RING completed deliveries (oldest
        first) — built on demand, the hot path stores raw traces."""
        return [tr.summary(t_end) for tr, t_end in list(self.recent)]

    def latency_snapshot(self) -> dict:
        """statistics_report()["latency"]: per-stream per-stage percentiles
        and per-query step percentiles, from the same histograms /metrics
        exports."""
        streams: dict[str, dict] = {}
        for (stream, stage), hist in self.stage_hist.samples():
            s = hist.summary()
            if s["count"]:
                streams.setdefault(stream, {})[stage] = s
        queries = {}
        for (query,), hist in self.query_hist.samples():
            s = hist.summary()
            if s["count"]:
                queries[query] = s
        lag = {stream: g.value()
               for (stream,), g in self.lag_gauge.samples()}
        return {"streams": streams, "queries": queries,
                "event_time_lag_s": lag}
