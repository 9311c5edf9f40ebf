"""Per-app shared services (reference: core/config/SiddhiAppContext.java:53).

The TPU build's context is much smaller: no thread pools or locks — execution
is single-controller and synchronous per micro-batch; state is functional. What
remains: the timestamp generator (wall clock vs playback virtual time,
reference core/util/timestamp/TimestampGeneratorImpl.java:31), the extension
registry snapshot, batching knobs, and statistics.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from ..extension.registry import Registry
from ..util.locks import named_lock, named_rlock
from . import dtypes

log = logging.getLogger("siddhi_tpu.stats")


class TimestampGenerator:
    """Wall-clock by default; in playback mode (@app:playback) time is driven
    by event timestamps (reference TimestampGeneratorImpl.java:78-131).

    @app:playback(idle.time='100 millisecond', increment='2 sec'): when the
    stream goes idle, `advance_idle()` bumps the virtual clock by `increment`
    (the reference runs this on a scheduled thread every idle.time; here the
    single-controller calls it from SiddhiAppRuntime.heartbeat)."""

    def __init__(self, playback: bool = False,
                 playback_increment_ms: int = 0,
                 idle_time_ms: Optional[int] = None) -> None:
        self.playback = playback
        self.playback_increment_ms = playback_increment_ms
        self.idle_time_ms = idle_time_ms
        self._observe_lock = named_lock("app.timestamp")
        self._last_event_ts: Optional[int] = None

    def current_time(self) -> int:
        if self.playback:
            if self._last_event_ts is None:
                return 0
            return self._last_event_ts
        return int(time.time() * 1000)

    def observe_event_time(self, ts: int) -> None:
        # multiple producer threads race this check-then-set; the watermark
        # must never regress (time-window expiry ordering depends on it)
        with self._observe_lock:
            if self._last_event_ts is None or ts > self._last_event_ts:
                self._last_event_ts = ts

    def advance_idle(self) -> int:
        """Playback idle bump: virtual clock += increment. Returns new time."""
        if self.playback and self._last_event_ts is not None:
            self._last_event_ts += self.playback_increment_ms
        return self.current_time()


@dataclass
class Statistics:
    """Per-app metrics (reference: core/util/statistics/ —
    SiddhiStatisticsManager.java:35-55 codahale registry, ThroughputTracker,
    LatencyTracker markIn/markOut, MemoryUsageTracker with deep object sizing,
    BufferedEventsTracker; levels OFF/BASIC/DETAIL, metrics/Level.java).

    BASIC: per-stream throughput + batch counts. DETAIL adds per-query latency
    and on-demand device-state memory (pytree nbytes replaces the reference's
    ObjectSizeCalculator walk) + staged-buffer depth (the Disruptor backlog
    analogue). Runtime-switchable via SiddhiAppRuntime.set_statistics_level
    (reference: SiddhiAppRuntimeImpl.setStatisticsLevel:868)."""

    enabled: bool = False
    level: str = "OFF"  # OFF | BASIC | DETAIL
    events_in: dict = field(default_factory=dict)  # stream -> count
    events_out: dict = field(default_factory=dict)
    batches: dict = field(default_factory=dict)
    query_latency_ns: dict = field(default_factory=dict)  # query -> (total, count)
    #: per-query XLA compile counter (query -> count) and the batch lane
    #: widths that triggered each trace (query -> [width, ...]). Tracked
    #: REGARDLESS of level: a recompile storm (unbounded shapes hitting a
    #: jitted step) stalls the pipeline for seconds per compile — it must be
    #: a visible metric, not a silent hang. Incremented at TRACE time from
    #: inside each runtime's step closure, so the count is exact per
    #: (query, shape-signature) executable.
    compiles: dict = field(default_factory=dict)
    compile_widths: dict = field(default_factory=dict)
    #: per-query step wall-time histogram: query -> {bucket_us: count} with
    #: power-of-two microsecond buckets (key = inclusive upper bound in us).
    #: DETAIL only — one bit_length per step.
    step_hist: dict = field(default_factory=dict)
    started_at: float = field(default_factory=time.time)
    #: capacity-overflow counters ("<runtime>.<structure>" -> lifetime rows
    #: dropped/overwritten/unresolved). Tracked regardless of level — silent
    #: capacity loss is a correctness signal, not a metric (SURVEY §7
    #: "overflow-to-host escape hatches"). Each counter warns once.
    overflow: dict = field(default_factory=dict)
    _overflow_warned: set = field(default_factory=set)
    #: fault-tolerance counters — tracked regardless of level, like overflow:
    #: a retried/dead-lettered/dropped event is a correctness signal operators
    #: must see without opting into metrics. sink_* keyed by stream id.
    sink_retries: dict = field(default_factory=dict)
    sink_dead_letters: dict = field(default_factory=dict)  # events stored
    sink_dropped: dict = field(default_factory=dict)  # events dropped (LOG)
    source_retries: dict = field(default_factory=dict)  # reconnect attempts
    recoveries: int = 0  # recover() completions
    wal_replayed: int = 0  # lifetime events re-sent by recover()
    shutdown_discarded: int = 0  # staged rows lost at shutdown()
    #: blue-green upgrade / historical-replay counters (core/upgrade.py) —
    #: tracked regardless of level: a swap or rollback is an operational
    #: event operators must see. cutover_pause_ms is the LAST swap's
    #: source-paused wall time (the headline "how long were we dark").
    upgrades: int = 0  # committed hot-swaps
    upgrade_rollbacks: int = 0  # failed swaps rolled back to v1
    upgrade_cutover_pause_ms: float = 0.0
    upgrade_wal_replayed: int = 0  # journal-tail events replayed into v2
    replay_runs: int = 0  # replay_wal() completions
    replay_events: int = 0  # lifetime events driven by replay_wal()
    #: overload-protection counters — tracked regardless of level, like the
    #: sink_* family: a dropped/diverted/paused event is a correctness signal.
    #: ingress_dropped is keyed stream -> {policy: rows} where policy is one
    #: of drop.new | drop.old | fault | block.timeout | source.pending.
    ingress_dropped: dict = field(default_factory=dict)
    bp_pauses: dict = field(default_factory=dict)  # stream -> pause() calls
    bp_resumes: dict = field(default_factory=dict)  # stream -> resume() calls
    queue_hwm: dict = field(default_factory=dict)  # stream -> max staged depth
    #: circuit-breaker counters, keyed by query name (state itself lives on
    #: the runtime's CircuitBreaker; report(runtime) merges both views)
    breaker_opens: dict = field(default_factory=dict)
    breaker_failures: dict = field(default_factory=dict)
    breaker_diverted: dict = field(default_factory=dict)  # rows diverted
    #: @app:eventTime rows diverted behind the watermark (kind="late"),
    #: keyed by stream — tracked regardless of level, like sink_*: a
    #: diverted row is a correctness signal, not a metric
    late_events: dict = field(default_factory=dict)
    #: one-retrace splice counters (core/shared.py splice_in/splice_out),
    #: keyed by kind: in | out | declined | failed — tracked regardless of
    #: level: a failed/declined splice is an operational event. The ms
    #: figure is the LAST successful splice's retrace+compile wall time.
    splices: dict = field(default_factory=dict)
    splice_retrace_ms: float = 0.0
    #: tenant device-time quota breaches, keyed by tenant id (core/tenant.py)
    tenant_breaches: dict = field(default_factory=dict)

    @property
    def detail(self) -> bool:
        return self.enabled and self.level == "DETAIL"

    def set_level(self, level: str) -> None:
        level = level.upper()
        if level not in ("OFF", "BASIC", "DETAIL"):
            raise ValueError(f"bad statistics level {level!r}")
        self.level = level
        self.enabled = level != "OFF"

    def track_in(self, stream_id: str, n: int) -> None:
        if self.enabled:
            self.events_in[stream_id] = self.events_in.get(stream_id, 0) + n

    def track_batch(self, stream_id: str) -> None:
        if self.enabled:
            self.batches[stream_id] = self.batches.get(stream_id, 0) + 1

    def track_latency(self, query: str, ns: int) -> None:
        if self.detail:
            t, c = self.query_latency_ns.get(query, (0, 0))
            self.query_latency_ns[query] = (t + ns, c + 1)
            bucket = 1 << max(ns // 1000, 1).bit_length()  # us, power of two
            h = self.step_hist.setdefault(query, {})
            h[bucket] = h.get(bucket, 0) + 1

    def track_compile(self, query: str, width: int) -> None:
        """One jitted-step TRACE (== one XLA compile) for `query` on a batch
        of `width` lanes. Called from inside the traced function body, so it
        fires exactly once per cached executable."""
        self.compiles[query] = self.compiles.get(query, 0) + 1
        self.compile_widths.setdefault(query, []).append(int(width))

    def track_sink_retry(self, stream_id: str) -> None:
        self.sink_retries[stream_id] = self.sink_retries.get(stream_id, 0) + 1

    def track_source_retry(self, stream_id: str) -> None:
        self.source_retries[stream_id] = \
            self.source_retries.get(stream_id, 0) + 1

    def track_dead_letter(self, stream_id: str, n: int) -> None:
        self.sink_dead_letters[stream_id] = \
            self.sink_dead_letters.get(stream_id, 0) + n

    def track_sink_drop(self, stream_id: str, n: int) -> None:
        self.sink_dropped[stream_id] = \
            self.sink_dropped.get(stream_id, 0) + n

    def track_ingress_drop(self, stream_id: str, policy: str, n: int) -> None:
        """Rows shed/diverted by a bounded junction (or a paused source's
        pending buffer) under `policy`. Exact by construction: every admission
        decision increments exactly one policy counter."""
        per = self.ingress_dropped.setdefault(stream_id, {})
        per[policy] = per.get(policy, 0) + n

    def track_pause(self, stream_id: str) -> None:
        self.bp_pauses[stream_id] = self.bp_pauses.get(stream_id, 0) + 1

    def track_resume(self, stream_id: str) -> None:
        self.bp_resumes[stream_id] = self.bp_resumes.get(stream_id, 0) + 1

    def track_queue_depth(self, stream_id: str, depth: int) -> None:
        if depth > self.queue_hwm.get(stream_id, 0):
            self.queue_hwm[stream_id] = depth

    def track_breaker_failure(self, query: str) -> None:
        self.breaker_failures[query] = self.breaker_failures.get(query, 0) + 1

    def track_breaker_open(self, query: str) -> None:
        self.breaker_opens[query] = self.breaker_opens.get(query, 0) + 1

    def track_breaker_divert(self, query: str, n: int) -> None:
        self.breaker_diverted[query] = self.breaker_diverted.get(query, 0) + n

    def track_splice(self, kind: str, retrace_ms: float = None) -> None:
        """kind: in | out | declined | failed. retrace_ms records the
        successful splice's trace+compile wall time (deploy latency)."""
        self.splices[kind] = self.splices.get(kind, 0) + 1
        if retrace_ms is not None:
            self.splice_retrace_ms = float(retrace_ms)

    def track_tenant_breach(self, tenant: str) -> None:
        self.tenant_breaches[tenant] = self.tenant_breaches.get(tenant, 0) + 1

    def track_late(self, stream_id: str, n: int) -> None:
        """Rows diverted to the ErrorStore as kind="late" (event time behind
        the watermark). Exact by construction: every gated row either
        delivers, buffers, or increments this once."""
        self.late_events[stream_id] = self.late_events.get(stream_id, 0) + n

    def track_recovery(self, replayed: int) -> None:
        self.recoveries += 1
        self.wal_replayed += replayed

    def track_shutdown_discard(self, n: int) -> None:
        self.shutdown_discarded += n

    def track_upgrade(self, cutover_pause_ms: float, replayed: int,
                      rollback: bool = False) -> None:
        if rollback:
            self.upgrade_rollbacks += 1
            return
        self.upgrades += 1
        self.upgrade_cutover_pause_ms = float(cutover_pause_ms)
        self.upgrade_wal_replayed += replayed

    def track_replay(self, events: int) -> None:
        self.replay_runs += 1
        self.replay_events += events

    def record_overflow(self, name: str, n: int) -> None:
        """Register a lifetime overflow counter reading; warns ONCE per
        counter the first time it goes positive (an @OnError-style signal —
        results past this point may be missing rows)."""
        if n <= 0:
            self.overflow.pop(name, None)
            return
        self.overflow[name] = n
        if name not in self._overflow_warned:
            self._overflow_warned.add(name)
            import warnings
            warnings.warn(
                f"{name}: {n} rows exceeded a fixed device capacity and "
                "were dropped/overwritten — results may be missing rows; "
                "raise the relevant capacity (see Statistics.report()"
                "['overflow'])", stacklevel=3)

    def reset(self) -> None:
        self.events_in.clear()
        self.events_out.clear()
        self.batches.clear()
        self.query_latency_ns.clear()
        self.compiles.clear()
        self.compile_widths.clear()
        self.step_hist.clear()
        self.overflow.clear()
        self.sink_retries.clear()
        self.sink_dead_letters.clear()
        self.sink_dropped.clear()
        self.source_retries.clear()
        self.ingress_dropped.clear()
        self.bp_pauses.clear()
        self.bp_resumes.clear()
        self.queue_hwm.clear()
        self.breaker_opens.clear()
        self.breaker_failures.clear()
        self.breaker_diverted.clear()
        self.late_events.clear()
        self.splices.clear()
        self.splice_retrace_ms = 0.0
        self.tenant_breaches.clear()
        self.recoveries = 0
        self.wal_replayed = 0
        self.shutdown_discarded = 0
        self.upgrades = 0
        self.upgrade_rollbacks = 0
        self.upgrade_cutover_pause_ms = 0.0
        self.upgrade_wal_replayed = 0
        self.replay_runs = 0
        self.replay_events = 0
        self.started_at = time.time()

    def report(self, runtime=None) -> dict:
        elapsed = max(time.time() - self.started_at, 1e-9)
        if runtime is not None:
            runtime.collect_overflow(report=True)
        out = {
            "level": self.level,
            "uptime_seconds": elapsed,
            "events_in": dict(self.events_in),
            "batches": dict(self.batches),
            "throughput_eps": {s: n / elapsed for s, n in self.events_in.items()},
            "overflow": dict(self.overflow),
            # always reported: a growing count under a steady workload is
            # the recompile-storm signature (see track_compile)
            "compiles": dict(self.compiles),
            "compile_widths": {q: list(w)
                               for q, w in self.compile_widths.items()},
            # fault-tolerance counters (always, like overflow: silent loss
            # is a correctness signal, not a metric)
            "sink_retries": dict(self.sink_retries),
            "sink_dead_letters": dict(self.sink_dead_letters),
            "sink_dropped": dict(self.sink_dropped),
            "source_retries": dict(self.source_retries),
            # overload protection (always, same rationale): drops by policy,
            # backpressure pause/resume counts, staged-depth high-watermarks
            "ingress_dropped": {s: dict(d)
                                for s, d in self.ingress_dropped.items()},
            "backpressure": {
                "pauses": dict(self.bp_pauses),
                "resumes": dict(self.bp_resumes),
                "queue_hwm": dict(self.queue_hwm),
            },
            "recovery": {
                "recoveries": self.recoveries,
                "wal_replayed": self.wal_replayed,
                "shutdown_discarded": self.shutdown_discarded,
            },
            "upgrade": {
                "upgrades": self.upgrades,
                "rollbacks": self.upgrade_rollbacks,
                "cutover_pause_ms": self.upgrade_cutover_pause_ms,
                "wal_tail_replayed": self.upgrade_wal_replayed,
            },
            "replay": {
                "runs": self.replay_runs,
                "events": self.replay_events,
            },
            # one-retrace membership churn (core/shared.py splice_in/out):
            # always reported — a failed or declined splice means a deploy
            # fell back to standalone dispatch, an operational event
            "splices": {
                "counts": dict(self.splices),
                "last_retrace_ms": self.splice_retrace_ms,
                "tenant_breaches": dict(self.tenant_breaches),
            },
            # always-on, like overflow: a serialized ingress pipeline is a
            # performance regression operators must see in production.
            # Populated below from the live pipelines (ring depth HWM,
            # worker utilization, h2d overlap ratio, per-stage wall time).
            "ingress_pipeline": {},
            # the async read-back (core/stream.py AsyncDecoder): batches
            # submitted and delivered, and per batch the wait for a fetch
            # worker, the fetch and the reorder wait + callback
            "readback": {},
        }
        if runtime is not None:
            for sid, j in runtime.junctions.items():
                p = getattr(j, "_pipeline", None)
                if p is not None:
                    out["ingress_pipeline"][sid] = p.stats_snapshot()
            if runtime.ctx.decoder is not None:
                out["readback"] = runtime.ctx.decoder.stats_snapshot()
            # join queries (core/join_runtime.py): steps per probe
            # direction, out-block and candidate lanes, the drop counter
            from .join_runtime import JoinQueryRuntime
            joins = {name: qr.stats_snapshot()
                     for name, qr in runtime.query_runtimes.items()
                     if isinstance(qr, JoinQueryRuntime)}
            if joins:
                out["joins"] = joins
            # pattern queries (core/pattern_runtime.py): steps per fed
            # stream, pending capacity and fill, expiries, the drop counter
            from .pattern_runtime import PatternQueryRuntime
            patterns = {name: qr.stats_snapshot()
                        for name, qr in runtime.query_runtimes.items()
                        if isinstance(qr, PatternQueryRuntime)}
            if patterns:
                out["patterns"] = patterns
            # queries over a sliding window (core/query_runtime.py): the
            # ring's capacity and fill, rows in and out, the loss counters
            from .query_runtime import QueryRuntime
            windows = {name: qr.stats_snapshot()
                       for name, qr in runtime.query_runtimes.items()
                       if isinstance(qr, QueryRuntime)
                       and qr.cells is not None}
            if windows:
                out["windows"] = windows
            # partitions on the keyed step (core/keyed_partition.py): steps,
            # key slots taken and stated, the lanes of keys turned away
            partitions = {name: pr.keyed.stats_snapshot()
                          for name, pr in runtime.partitions.items()
                          if pr.keyed is not None}
            if partitions:
                out["partitions"] = partitions
            # tables on the device index (core/table_index.py): rows taken
            # and stated, inserts, updates, drops, duplicates, probes, hits
            tables = {name: t.stats_snapshot()
                      for name, t in runtime.tables.items()
                      if getattr(t, "index_key", None) is not None}
            if tables:
                out["tables"] = tables
        if runtime is not None:
            wal = getattr(runtime, "wal", None)
            if wal is not None:
                out["recovery"]["wal_appended"] = wal.appended_events
                out["recovery"]["wal_records"] = wal.appended_records
            es = getattr(runtime.ctx, "error_store", None)
            if es is not None and hasattr(es, "dropped_count"):
                out["error_store"] = {
                    "entries": len(es.load(runtime.app.name)),
                    "dropped_error_entries":
                        es.dropped_count(runtime.app.name),
                }
            wms = {}
            for sid, j in runtime.junctions.items():
                et = getattr(j, "_et", None)
                if et is not None:
                    wms[sid] = et.snapshot()
            if wms:
                # event-time gates (core/event_time.py): watermark position,
                # reorder-buffer depth, and the exactly-once accounting
                # (admitted == released + late + buffered)
                out["watermarks"] = wms
            if self.late_events:
                out["late_events"] = dict(self.late_events)
            breakers = {}
            for name, qr in runtime.query_runtimes.items():
                br = getattr(qr, "breaker", None)
                if br is None:
                    continue
                breakers[name] = {
                    **br.snapshot(),
                    "failures": self.breaker_failures.get(name, 0),
                    "diverted_rows": self.breaker_diverted.get(name, 0),
                }
            if breakers:
                out["breakers"] = breakers
            tenants = getattr(runtime, "tenants", None)
            if tenants is not None:
                # per-tenant quota accounting (core/tenant.py): rolling
                # device-ms spend vs budget, breach counts, diverted rows
                out["tenants"] = tenants.report(self)
            tele = getattr(runtime.ctx, "telemetry", None)
            if tele is not None:
                # always-on (independent of statistics level): the batch
                # tracer's per-stage/per-query percentiles and the worst-N
                # slow-batch exemplars — same histograms /metrics exports
                out["latency"] = tele.latency_snapshot()
                out["slow_batches"] = tele.slow_batches()
            eng = getattr(runtime, "slo_engine", None)
            if eng is not None:
                # declared objectives + both burn windows + breach state
                # (telemetry/slo.py; same data GET /slo serves)
                out["slo"] = eng.report()
            rec = getattr(runtime.ctx, "recorder", None)
            if rec is not None:
                out["recorder"] = rec.report()
            opt = getattr(runtime, "optimizer_report", None)
            if opt is not None:
                # multi-query shared execution (core/shared.py): fused-group
                # inventory from creation time, plus the live compile-savings
                # number — each group compile replaces len(members) per-query
                # compiles of the same shape
                groups = getattr(runtime, "shared_groups", ())
                out["optimizer"] = {
                    **opt,
                    "compiles_avoided": sum(
                        self.compiles.get(g.name, 0) * (len(g.members) - 1)
                        for g in groups),
                }
            else:
                out["optimizer"] = {"enabled": False}
            try:
                # static cost prediction vs live telemetry (analysis/cost.py
                # + measure_runtime_state_bytes): the calibration pair that
                # tools/cost_calibrate.py gates on in CI
                from ..analysis.cost import measure_runtime_state_bytes
                pred = runtime.cost_report
                live = measure_runtime_state_bytes(runtime)
                live_bytes = sum(live.values())
                live_compiles = sum(self.compiles.values())
                out["cost"] = {
                    "predicted_state_bytes": pred["predicted_state_bytes"],
                    "live_state_bytes": live_bytes,
                    "state_ratio": (live_bytes /
                                    pred["predicted_state_bytes"]
                                    if pred["predicted_state_bytes"] else
                                    None),
                    "predicted_compiles": pred["predicted_compiles"],
                    "live_compiles": live_compiles,
                    "exact": pred["exact"],
                    "dominant": pred.get("dominant"),
                    "budget": pred.get("budget"),
                    "live_elements": live,
                }
            except Exception:  # advisory — never break a stats report
                log.debug("cost section crashed", exc_info=True)
            lint = getattr(runtime, "lint_report", None)
            if lint is not None:
                # what the SIDDHI_LINT gate saw at creation: rule counts +
                # severity totals (full diagnostics via the lint CLI/REST)
                out["lint"] = {
                    "valid": not lint.has_errors,
                    "errors": len(lint.errors),
                    "warnings": len(lint.warnings),
                    "rules": lint.rule_counts(),
                }
        from ..util import locks as _locks
        if _locks.checks_enabled():
            # lockdep findings (util/locks.py): acquisition-order cycles +
            # held-across-blocking hazards, only under SIDDHI_LOCK_CHECKS=1
            out["lockdep"] = _locks.lockdep_report()
        if self.detail:
            out["query_latency_ms"] = {
                q: (t / c / 1e6 if c else 0.0)
                for q, (t, c) in self.query_latency_ns.items()}
            out["step_time_hist_us"] = {
                q: dict(sorted(h.items())) for q, h in self.step_hist.items()}
            if runtime is not None:
                out["state_memory_bytes"] = {
                    name: _pytree_nbytes(qr.state)
                    for name, qr in runtime.query_runtimes.items()}
                out["buffered_events"] = {
                    sid: len(j._staged_rows) + len(j._tap_queue)
                    for sid, j in runtime.junctions.items()}
        return out


def _pytree_nbytes(tree) -> int:
    """Deep device-state size — replaces the reference's
    ObjectSizeCalculator (core/util/statistics/memory/)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += getattr(leaf, "nbytes", 0) or 0
    return total


@dataclass
class SiddhiAppContext:
    name: str
    registry: Registry
    timestamp_generator: TimestampGenerator
    batch_size: int = 0  # 0 = dtypes.config.default_batch_size
    group_capacity: int = 0
    #: jax.sharding.Mesh for SPMD partition execution (None = host routing)
    mesh: object = None
    partition_capacity: int = 0  # key slots for mesh partitions; 0 = default
    statistics: Statistics = field(default_factory=Statistics)
    playback: bool = False
    #: root runtime back-reference (set by SiddhiAppRuntime)
    runtime: object = None
    #: app-global string interning table shared by every codec (stream, table,
    #: window, query output) so dictionary codes are consistent app-wide
    global_strings: object = None
    #: single-controller gate: async feeder threads and user-thread
    #: flush/heartbeat/query serialize device work through this RLock (the
    #: role of the reference's ThreadBarrier + per-query locks)
    controller_lock: object = field(
        default_factory=lambda: named_rlock("app.controller"))
    #: async stream-callback decode (create_siddhi_app_runtime(...,
    #: async_callbacks=True)): device→host readback + Event decode run on a
    #: dedicated worker so the controller thread never blocks on the
    #: device→host round trip. Opt-in
    #: because it changes visible semantics: flush() may return before
    #: callbacks ran — runtime.drain() is the barrier.
    async_callbacks: bool = False
    decoder: object = None
    #: telemetry.AppTelemetry — always-on metrics registry + batch tracer
    #: (set by SiddhiAppRuntime before any junction is built)
    telemetry: object = None
    #: telemetry.FlightRecorder — always-on evidence ring + anomaly-triggered
    #: diagnostic bundles (set by SiddhiAppRuntime after build)
    recorder: object = None
    #: event_time.EventTimeConfig parsed from @app:eventTime (None = arrival
    #: time); read by query runtimes (window lateness) and ingress gates
    event_time: object = None
    #: device-resident supersteps (@app:superstep(k=) / SIDDHI_SUPERSTEP_K):
    #: the async ingress feeder stages this many ring slots into one [K, B]
    #: chunk and runs the query chain as a single lax.scan dispatch
    #: (core/superstep.py). 1 = off; ineligible plans fall back loudly.
    superstep_k: int = 1
    #: tenant.TenantRegistry when the app declares @app:tenant quotas —
    #: the ALWAYS-ON device-time meter both dispatch paths feed (unlike
    #: track_latency it is not gated on statistics detail, because quota
    #: enforcement reads it)
    tenant_meter: object = None

    @property
    def effective_batch_size(self) -> int:
        return self.batch_size or dtypes.config.default_batch_size

    @property
    def effective_group_capacity(self) -> int:
        return self.group_capacity or dtypes.config.default_group_capacity

    @property
    def effective_partition_capacity(self) -> int:
        return self.partition_capacity or dtypes.config.default_partition_capacity
