"""SiddhiAppRuntime — one planned, running app.

Reference: core/SiddhiAppRuntimeImpl.java:103 (junction map:124, query map:122,
start():449, shutdown():552, persist():686). The TPU build keeps the same user
surface but execution is synchronous single-controller: sends stage rows into
junction buffers; flush() drives every staged batch through the jitted query
pipeline and cascades device-to-device until quiescent.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

from ..errors import (
    DefinitionNotExistError,
    SiddhiAppCreationError,
    SiddhiAppRuntimeError,
)
from ..extension.registry import Registry
from ..query_api import Query, SiddhiApp, StreamDefinition
from ..query_api.execution import OutputAction, SingleInputStream
from .context import SiddhiAppContext, Statistics, TimestampGenerator
from .event import StreamCodec
from .query_runtime import FunctionQueryCallback, QueryCallback, QueryRuntime
from .stream import (
    FunctionStreamCallback,
    InputHandler,
    StreamCallback,
    StreamJunction,
)


class WarmupResult(dict):
    """`SiddhiAppRuntime.warmup()`'s answer: {query_name: fresh compiles},
    plus `failures` — {query_name: exception} for every step that did not
    compile."""

    def __init__(self) -> None:
        super().__init__()
        self.failures: dict[str, Exception] = {}


class SiddhiAppRuntime:
    def __init__(self, app: SiddhiApp, registry: Registry,
                 batch_size: int = 0, group_capacity: int = 0,
                 error_store=None, config_manager=None,
                 mesh=None, partition_capacity: int = 0,
                 async_callbacks: bool = False,
                 auto_flush_ms: Optional[float] = None,
                 aot_warmup: bool = False,
                 wal_dir: Optional[str] = None,
                 persistence_interval_s: Optional[float] = None,
                 optimize: Optional[bool] = None) -> None:
        self.app = app
        #: LintReport attached by SiddhiManager's SIDDHI_LINT gate
        #: (None when linting is off or the app was built directly)
        self.lint_report = None
        #: AOT-compile every query's step ladder at start() (also
        #: SIDDHI_AOT_WARMUP=1) so the first real batch never pays
        #: first-compile latency — see warmup()
        import os as _os
        self.aot_warmup = aot_warmup or \
            _os.environ.get("SIDDHI_AOT_WARMUP", "") not in ("", "0")
        playback_ann = app.annotation("app:playback")
        idle_ms = increment_ms = None
        if playback_ann is not None:
            from .partition import _parse_annotation_time
            idle = playback_ann.element("idle.time")
            inc = playback_ann.element("increment")
            idle_ms = _parse_annotation_time(idle) if idle else None
            increment_ms = _parse_annotation_time(inc) if inc else None
            if increment_ms is None and idle_ms is not None:
                increment_ms = idle_ms  # idle.time alone: bump by itself
        self.ctx = SiddhiAppContext(
            name=app.name,
            registry=registry,
            timestamp_generator=TimestampGenerator(
                playback=playback_ann is not None,
                playback_increment_ms=increment_ms or 0,
                idle_time_ms=idle_ms),
            batch_size=batch_size,
            group_capacity=group_capacity,
            mesh=mesh,
            partition_capacity=partition_capacity,
            playback=playback_ann is not None,
        )
        self.ctx.runtime = self
        self.ctx.async_callbacks = async_callbacks
        # wall-clock auto-flush — the Disruptor's immediate-consumption role
        # (reference: StreamJunction.java:68 batchSize knob +
        # core/util/Scheduler.java:48 timer re-entry): staged rows are
        # flushed within ~auto_flush_ms without the caller polling flush().
        # Enable per runtime (kwarg) or per app (@app:autoFlush('10 ms')).
        af_ann = app.annotation("app:autoFlush")
        if auto_flush_ms is None and af_ann is not None:
            from .partition import _parse_annotation_time
            v = af_ann.element("interval") or af_ann.element()
            auto_flush_ms = _parse_annotation_time(v) if v else 10.0
        self.auto_flush_ms = auto_flush_ms
        self._flusher_stop = None
        self._flusher_thread = None
        # crash recovery: @app:persist(interval='30 sec', wal.dir='/var/wal')
        # or the wal_dir / persistence_interval_s kwargs — a periodic
        # persistence scheduler plus a write-ahead ingress journal so
        # recover() = restore_last_revision() + WAL replay (state/wal.py)
        persist_ann = app.annotation("app:persist")
        if persist_ann is not None:
            from .partition import _parse_annotation_time
            iv = persist_ann.element("interval") or persist_ann.element()
            if persistence_interval_s is None and iv:
                persistence_interval_s = _parse_annotation_time(iv) / 1000.0
            wd = persist_ann.element("wal.dir")
            if wal_dir is None and wd:
                wal_dir = wd
        self.persistence_interval_s = persistence_interval_s
        self._persist_stop = None
        self._persist_thread = None
        self._recovering = False
        self.wal = None
        if wal_dir:
            from ..state.wal import WriteAheadLog
            self.wal = WriteAheadLog(wal_dir, app.name)
        self.ctx.error_store = error_store
        self.ctx.config_manager = config_manager
        # out-of-order event time: @app:eventTime(timestamp='ts',
        # allowed.lateness='5 sec', idle.timeout='1 min') — parsed BEFORE
        # _build() (query runtimes read ctx.event_time to put externalTime
        # windows into watermark-driven emission), gates attached AFTER
        # (they hang off the built ingress junctions)
        et_ann = app.annotation("app:eventTime")
        self.ctx.event_time = None
        if et_ann is not None:
            from .event_time import EventTimeConfig
            from .partition import _parse_annotation_time
            attr = et_ann.element("timestamp") or et_ann.element()
            if not attr:
                raise SiddhiAppCreationError(
                    "@app:eventTime needs a timestamp attribute: "
                    "@app:eventTime(timestamp='ts', ...)")
            lat = et_ann.element("allowed.lateness")
            idle = et_ann.element("idle.timeout")
            self.ctx.event_time = EventTimeConfig(
                attr=attr,
                lateness_ms=int(_parse_annotation_time(lat)) if lat else 0,
                idle_timeout_ms=int(_parse_annotation_time(idle))
                if idle else None)
        from .event import StringTable
        self.ctx.global_strings = StringTable()
        from ..telemetry import AppTelemetry
        self.ctx.telemetry = AppTelemetry(app.name)
        self._owns_jax_trace = False
        stats_ann = app.annotation("app:statistics")
        if stats_ann is not None:
            # @app:statistics('true'|'BASIC'|'DETAIL') (reference:
            # SiddhiAppParser.java:113-148, metrics/Level.java)
            val = (stats_ann.element() or "BASIC").upper()
            level = {"TRUE": "BASIC", "FALSE": "OFF"}.get(val, val)
            self.ctx.statistics = Statistics()
            try:
                self.ctx.statistics.set_level(level)
            except ValueError as e:
                raise SiddhiAppCreationError(str(e)) from e

        # device-resident supersteps: @app:superstep(k='8') batches K async
        # ingress chunks into one lax.scan dispatch (core/superstep.py).
        # Env SIDDHI_SUPERSTEP_K overrides the annotation (bench sweeps, CI
        # parity runs); ineligible plans decline loudly at first dispatch.
        ss_k = 1
        ss_ann = app.annotation("app:superstep")
        if ss_ann is not None:
            v = ss_ann.element("k") or ss_ann.element()
            try:
                ss_k = int(v) if v else 1
            except ValueError as e:
                raise SiddhiAppCreationError(
                    f"@app:superstep k must be an integer, got {v!r}") from e
        env_k = os.environ.get("SIDDHI_SUPERSTEP_K", "").strip()
        if env_k:
            try:
                ss_k = int(env_k)
            except ValueError:
                pass
        self.ctx.superstep_k = max(1, ss_k)

        self.junctions: dict[str, StreamJunction] = {}
        self.input_handlers: dict[str, InputHandler] = {}
        self.query_runtimes: dict[str, QueryRuntime] = {}
        self.tables: dict = {}
        self.windows: dict = {}
        self.triggers: dict = {}
        self.aggregations: dict = {}
        self.partitions: dict = {}
        self.sources: list = []
        self.sinks: list = []
        self.fault_junctions: dict[str, StreamJunction] = {}
        self._started = False

        # multi-tenant quotas (@app:tenant + per-query @tenant): registry
        # wired BEFORE _build() so build-time assignment enforces queries=
        # quotas, and onto the context as the always-on device-time meter
        from .tenant import tenants_from_app
        self.tenants = tenants_from_app(app)
        self.ctx.tenant_meter = self.tenants
        if self.tenants is not None:
            self.tenants.bind_telemetry(self.ctx.telemetry)
        #: bumped by every attach/detach (splice churn) — the flusher loop
        #: and other cached plan-shape decisions recompute when it moves
        self._plan_epoch = 0
        self._splice_seq = 0

        self._build()

        # multi-query shared execution (@app:optimize / SIDDHI_OPTIMIZE /
        # the optimize kwarg): fuse co-resident queries into shared compiled
        # steps AFTER the runtimes exist but BEFORE any traffic or warmup —
        # self.app stays the pre-optimization app, so plan fingerprints,
        # snapshots, and upgrade diffs see the unfused layout
        self.shared_groups: list = []
        self.optimizer_report: Optional[dict] = None
        from ..analysis.optimizer import optimizer_enabled
        if optimizer_enabled(app, optimize):
            from .shared import build_shared_groups
            self.optimizer_report = build_shared_groups(self)

        if self.wal is not None:
            # journal INGRESS junctions only: user-defined streams take rows
            # from outside the engine; derived/trigger/fault streams are
            # reproducible from their inputs
            for sid in app.stream_definitions:
                self.junctions[sid].wal = self.wal

        if self.ctx.event_time is not None:
            # event-time gates on INGRESS junctions carrying the annotated
            # attribute (derived streams inherit sorted order from their
            # inputs, so they never gate). WAL note: rows journal at send
            # time, BEFORE the gate — replay re-runs them through it, so
            # buffered/late classification survives a crash.
            cfg = self.ctx.event_time
            from ..query_api.definition import AttributeType as _AT
            gated = 0
            for sid, sd in app.stream_definitions.items():
                attr = next((a for a in sd.attributes
                             if a.name == cfg.attr), None)
                if attr is None:
                    continue
                if attr.type not in (_AT.INT, _AT.LONG):
                    raise SiddhiAppCreationError(
                        f"@app:eventTime: attribute {cfg.attr!r} on stream "
                        f"{sid!r} must be INT or LONG (epoch ms), got "
                        f"{attr.type.name}")
                self.junctions[sid].attach_event_time(cfg)
                gated += 1
            if gated == 0:
                raise SiddhiAppCreationError(
                    f"@app:eventTime: no stream defines the timestamp "
                    f"attribute {cfg.attr!r}")

        # SLO engine (@app:slo / per-query @slo; None when undeclared) and
        # the always-on flight recorder — built AFTER _build() so objective
        # binding can resolve query names and the recorder can snapshot a
        # fully-wired runtime. SLO breaches trigger the recorder.
        from ..telemetry.recorder import FlightRecorder
        from ..telemetry.slo import slo_engine_from_app
        diag_ann = app.annotation("app:diagnostics")
        diag_dir = diag_ann.element("dir") if diag_ann is not None else None
        self.ctx.recorder = FlightRecorder(self, bundle_dir=diag_dir)
        self.slo_engine = slo_engine_from_app(self)
        if self.slo_engine is not None:
            rec = self.ctx.recorder
            self.slo_engine.on_breach = lambda o, ev: rec.trigger(
                "slo_breach", reason=f"{o.id} burn fast="
                f"{o.last_fast.get('burn_rate', 0):.2f} slow="
                f"{o.last_slow.get('burn_rate', 0):.2f}")
        self._slo_stop = None
        self._slo_thread = None

    # ------------------------------------------------------------------ build

    def _build(self) -> None:
        app, ctx = self.app, self.ctx

        if app.function_definitions:
            # app-scoped registry: `define function` must not leak across apps
            ctx.registry = ctx.registry.copy()
            from .function import bind_app_functions
            bind_app_functions(app, ctx.registry)

        from ..io.wiring import build_sink, build_source
        from ..query_api.definition import Attribute, AttributeType
        for sd in app.stream_definitions.values():
            junction = StreamJunction(sd, ctx)
            self.junctions[sd.id] = junction
            if junction.on_error_action == "stream":
                # `!stream` fault junction: original attrs + _error message
                # (reference: StreamJunction fault streams :371-463); the
                # `fault` overflow policy and breaker diverts route through
                # it too when the stream declares @OnError(action='STREAM')
                fd = StreamDefinition(
                    id=f"!{sd.id}",
                    attributes=tuple(sd.attributes)
                    + (Attribute("_error", AttributeType.STRING),))
                junction.fault_junction = StreamJunction(fd, ctx)
                self.fault_junctions[sd.id] = junction.fault_junction
            for ann in sd.annotations or ():
                if ann.name.lower() == "source":
                    self.sources.append(build_source(ann, junction, ctx))
                elif ann.name.lower() == "sink":
                    self.sinks.append(build_sink(ann, junction, ctx))

        from .table import InMemoryTable
        for td in app.table_definitions.values():
            store_ann = (td.annotation("store") or td.annotation("Store")) \
                if td.annotations else None
            if store_ann is not None:
                from ..io.record_table import RecordTableRuntime
                self.tables[td.id] = RecordTableRuntime(
                    td, ctx, self.ctx.registry)
            else:
                from .table import refuse_masks
                from .table_index import index_refusal, inserted_by
                table = self.tables[td.id] = InMemoryTable(
                    td, ctx, indexed=index_refusal(td, app) is None)
                if table.primary_keys and table.index_key is None \
                        and inserted_by(app, td.id):
                    # the columnar insert's [B, C] and [B, B] key masks
                    B = ctx.effective_batch_size
                    refuse_masks(f"insert into table {td.id!r} (a primary "
                                 "key on the [B, C] path)",
                                 B * (table.capacity + B))

        from .window import NamedWindow
        for wd in app.window_definitions.values():
            self.windows[wd.id] = NamedWindow(wd, ctx, self.ctx.registry)

        from .trigger import TriggerRuntime, trigger_stream_definition
        for td in app.trigger_definitions.values():
            sd = trigger_stream_definition(td)
            self.junctions[sd.id] = StreamJunction(sd, ctx)
            self.triggers[td.id] = TriggerRuntime(td, self.junctions[sd.id], ctx)

        from .aggregation import AggregationRuntime
        for ad in app.aggregation_definitions.values():
            junction = self.junctions.get(ad.input_stream_id)
            if junction is None:
                raise DefinitionNotExistError(
                    f"aggregation {ad.id!r}: stream {ad.input_stream_id!r} "
                    "is not defined")
            self.aggregations[ad.id] = AggregationRuntime(
                ad, ctx, junction, self.ctx.registry)

        for i, query in enumerate(app.queries):
            self._add_query(query, f"query{i + 1}")

        from .partition import PartitionRuntime
        for i, p in enumerate(app.partitions):
            pr = PartitionRuntime(p, self, i + 1)
            self.partitions[pr.name] = pr

    def _add_query(self, query: Query, default_name: str) -> None:
        from ..query_api.execution import JoinInputStream
        name = query.name or default_name

        if self.tenants is not None:
            # quota check BEFORE any runtime state exists: an over-quota
            # tenant's attach raises here with nothing to unwind
            from .tenant import query_tenant
            tid = query_tenant(query)
            if tid is not None:
                self.tenants.assign(name, tid)

        from ..query_api.execution import StateInputStream
        if isinstance(query.input_stream, JoinInputStream):
            qr = self._add_join_query(query, name)
        elif isinstance(query.input_stream, StateInputStream):
            qr = self._add_pattern_query(query, name)
        elif isinstance(query.input_stream, SingleInputStream):
            sid = query.input_stream.stream_id
            if query.input_stream.is_fault:
                junction = self.fault_junctions.get(sid)
                if junction is None:
                    raise DefinitionNotExistError(
                        f"stream {sid!r} has no fault stream (add "
                        "@OnError(action='STREAM'))")
                qr = QueryRuntime(query, self.ctx, junction, self.ctx.registry,
                                  name=name, tables=self.tables)
                junction.subscribe(qr)
                self.query_runtimes[name] = qr
                self._wire_output(qr, query)
                return
            junction = self.junctions.get(sid)
            if junction is None and sid in self.windows:
                # `from W ...` consumes the named window's emissions
                # (reference: WindowWindowProcessor via core/window/Window.java)
                if query.input_stream.handlers.window is not None:
                    raise SiddhiAppCreationError(
                        f"named window {sid!r} cannot take a further window "
                        "in FROM (a window cannot be windowed)")
                junction = self.windows[sid].output_junction
            if junction is None:
                raise DefinitionNotExistError(f"stream {sid!r} is not defined")
            qr = QueryRuntime(query, self.ctx, junction, self.ctx.registry,
                              name=name, tables=self.tables)
            junction.subscribe(qr)
        else:
            raise SiddhiAppCreationError(
                f"{type(query.input_stream).__name__} queries are not yet supported")
        if getattr(qr, "breaker", None) is None:
            # join/pattern runtimes don't build one themselves; single-input
            # QueryRuntime already did (core/breaker.py)
            from .breaker import breaker_from_annotations
            qr.breaker = breaker_from_annotations(query, name=name)
        self.query_runtimes[name] = qr

        self._wire_output(qr, query)

    def _add_join_query(self, query: Query, name: str):
        from .join_runtime import JoinQueryRuntime, _JoinSideReceiver
        qr = JoinQueryRuntime(query, self.ctx, self.junctions, self.tables,
                              self.ctx.registry, name, windows=self.windows,
                              aggregations=self.aggregations)
        if qr.left.junction is not None:
            qr.left.junction.subscribe(_JoinSideReceiver(qr, True))
        if qr.right.junction is not None:
            qr.right.junction.subscribe(_JoinSideReceiver(qr, False))
        return qr

    def _add_pattern_query(self, query: Query, name: str):
        from .pattern_runtime import (MERGED_SID, PatternQueryRuntime,
                                      _PatternSideReceiver)
        qr = PatternQueryRuntime(query, self.ctx, self.junctions, self.tables,
                                 self.ctx.registry, name)
        if qr.merged_junction is not None:
            # multi-stream sequence: the tagged merged junction (fed by
            # send-order taps on the sources) is the only feed; register it
            # so flush()/shutdown drive it like any other junction
            qr.merged_junction.subscribe(_PatternSideReceiver(qr, MERGED_SID))
            self.junctions[qr.merged_junction.definition.id] = \
                qr.merged_junction
        else:
            for sid in qr.junctions:
                qr.junctions[sid].subscribe(_PatternSideReceiver(qr, sid))
        return qr

    def _wire_output(self, qr, query: Query) -> None:
        out = query.output_stream
        if out.action == OutputAction.INSERT and out.is_fault and out.target_id:
            target = self.fault_junctions.get(out.target_id)
            if target is None:
                raise DefinitionNotExistError(
                    f"stream {out.target_id!r} has no fault stream (add "
                    "@OnError(action='STREAM'))")
            qr.output_junction = target
            return
        if out.action == OutputAction.INSERT and out.target_id:
            if out.target_id in self.tables:
                table = self.tables[out.target_id]
                # unionSet-projection provenance flows into the table: the
                # inserted column carries the set-size projection, so
                # downstream sizeOfSet(T.attr) stays readable (and ordinary
                # LONG columns stay rejected)
                marks = {n for n in getattr(qr.selector, "host_set_slots", {})
                         if n in table.attr_types}
                if marks:
                    table.set_projection_attrs = (
                        set(getattr(table, "set_projection_attrs", ()) or ())
                        | marks)
                qr.output_junction = _TableJunctionAdapter(table)
            elif out.target_id in self.windows:
                from .window import WindowJunctionAdapter
                qr.output_junction = WindowJunctionAdapter(
                    self.windows[out.target_id],
                    out_types=qr.selector.out_types)
            else:
                target = self.junctions.get(out.target_id)
                if target is None:
                    # auto-define the output stream from the select list
                    # (reference: OutputParser infers output stream definitions)
                    sd = qr.output_definition
                    target = StreamJunction(sd, self.ctx, codec=qr.output_codec)
                    self.junctions[sd.id] = target
                qr.output_junction = target
        elif out.action in (OutputAction.DELETE, OutputAction.UPDATE,
                            OutputAction.UPDATE_OR_INSERT):
            from ..io.record_table import (RecordTableOutputExecutor,
                                           RecordTableRuntime)
            from .table import TableOutputExecutor
            table = self.tables.get(out.target_id)
            if table is None:
                raise DefinitionNotExistError(f"table {out.target_id!r} is not defined")
            aliases = [getattr(query.input_stream, "stream_id", None),
                       getattr(query.input_stream, "reference_id", None)]
            from .table import IndexedTableWriter, refuse_masks
            if isinstance(table, RecordTableRuntime):
                executor_cls = RecordTableOutputExecutor
            elif getattr(table, "index_key", None) is not None:
                # index_refusal admitted every writer of the table
                executor_cls = IndexedTableWriter
            else:
                executor_cls = TableOutputExecutor
            qr.table_executor = executor_cls(
                table, out, qr.selector.out_types, qr.output_codec,
                self.ctx.registry, out_frame_aliases=aliases)
            if executor_cls is TableOutputExecutor:
                refuse_masks(
                    f"{out.action.name.lower().replace('_', ' ')} into table "
                    f"{out.target_id!r} (query {qr.name!r})",
                    qr.table_executor.mask_bytes(
                        self.ctx.effective_batch_size))

    # ------------------------------------------------------ churn (splice)
    #
    # attach_query/detach_query are the no-stop-the-world deploy path:
    # membership changes splice into/out of the live SharedStepGroup
    # (core/shared.py) with ONE retrace and sibling queries undisturbed —
    # no drain, no rebuild of anything but the fused jit. Splice-ineligible
    # queries fall back LOUDLY to standalone dispatch (the pre-splice
    # behaviour) and the reason lands in optimizer_report["splice_declined"].

    def _all_junctions(self) -> list:
        js = list(self.junctions.values())
        js += list(self.fault_junctions.values())
        js += [w.output_junction for w in self.windows.values()
               if getattr(w, "output_junction", None) is not None]
        return js

    def attach_query(self, query, *, name: Optional[str] = None,
                     state: Optional[bytes] = None) -> dict:
        """Attach one query to the RUNNING app. `query` is SiddhiQL text
        (single query) or a parsed Query. `state` optionally seeds the new
        query's state tensors via the per-element restore primitive
        (state/persistence.py — same path upgrades migrate state through).
        Returns {"name", "deploy_ms", "fused", ...}; raises
        SiddhiAppCreationError (bad query / tenant quota) without touching
        the live plan."""
        import time as _time
        if isinstance(query, str):
            from .. import compiler
            query = compiler.parse_query(query)
        t0 = _time.perf_counter_ns()
        with self.ctx.controller_lock:
            qname = query.name or name
            if qname is None:
                i = len(self.query_runtimes) + 1
                while f"query{i}" in self.query_runtimes:
                    i += 1
                qname = f"query{i}"
            if qname in self.query_runtimes:
                raise SiddhiAppCreationError(
                    f"query {qname!r} is already attached")
            # transactional wiring: snapshot receiver lists + junction map
            # so a failed attach (bad output target, quota...) unwinds to
            # the exact pre-attach plan
            recv_snap = [(j, list(j.receivers)) for j in
                         self._all_junctions()]
            junc_snap = dict(self.junctions)
            try:
                self._add_query(query, qname)
            except BaseException:
                self.query_runtimes.pop(qname, None)
                if self.tenants is not None:
                    self.tenants.release(qname)
                self.junctions.clear()
                self.junctions.update(junc_snap)
                for j, receivers in recv_snap:
                    j.receivers[:] = receivers
                raise
            qr = self.query_runtimes[qname]
            self.app.execution_elements.append(query)
            self._cost_report = None
            if state is not None:
                self.restore(state, elements={"queries": {qname}})
            splice = self._try_splice_in(qr)
            self._plan_epoch += 1
        deploy_ms = (_time.perf_counter_ns() - t0) / 1e6
        return {"name": qname, "deploy_ms": deploy_ms, **splice}

    def detach_query(self, name: str) -> dict:
        """Detach a query from the RUNNING app: spliced out of its fused
        group (siblings keep running; the departing step body is DCE'd on
        the one retrace) or simply unsubscribed when standalone. Raises
        KeyError for an unknown query."""
        import time as _time
        t0 = _time.perf_counter_ns()
        with self.ctx.controller_lock:
            qr = self.query_runtimes[name]
            if getattr(qr, "_fused_group", None) is not None:
                self._unfuse_query(qr, keep=False)
            for j in self._all_junctions():
                j.receivers[:] = [
                    r for r in j.receivers
                    if r is not qr and getattr(r, "runtime", None) is not qr]
            self.query_runtimes.pop(name, None)
            q = qr.query
            self.app.execution_elements[:] = [
                e for e in self.app.execution_elements if e is not q]
            if self.tenants is not None:
                self.tenants.release(name)
            self._cost_report = None
            self._plan_epoch += 1
        return {"name": name,
                "detach_ms": (_time.perf_counter_ns() - t0) / 1e6}

    def _try_splice_in(self, qr) -> dict:
        """One-retrace splice of a freshly attached (or quota-recovered)
        standalone receiver into a fused group on its junction: an
        existing group with room, else a NEW group formed from the
        trailing run of spliceable standalone receivers. Never raises —
        failure/decline leaves `qr` standalone (the loud fallback) and
        returns why."""
        from ..analysis.optimizer import SPLICE_DECLINE_NO_GROUP
        from .shared import SharedStepGroup, group_cap, runtime_decline
        if self.optimizer_report is None:
            return {"fused": False}  # optimizer off: standalone by design
        junction = getattr(qr, "input_junction", None)
        reason = runtime_decline(qr)
        if reason is None and junction is None:
            reason = SPLICE_DECLINE_NO_GROUP
        group = None
        if reason is None:
            for g in self.shared_groups:
                if g.junction is not junction:
                    continue
                r = g.splice_decline(qr)
                if r is None:
                    group = g
                    break
                reason = r
        if group is not None:
            try:
                ms = group.splice_in(qr)
            except Exception as e:  # noqa: BLE001 — group rolled back
                self._splice_failed(f"splice_in {qr.name} -> "
                                    f"{group.name}: {e}")
                return {"fused": False, "failed": str(e)}
            junction.receivers[:] = [r for r in junction.receivers
                                     if r is not qr]
            self._track_splice("in", ms)
            self._refresh_optimizer_report()
            return {"fused": True, "group": group.name, "retrace_ms": ms}
        # no group with room: try forming a new one from the trailing
        # contiguous run of spliceable standalones (contiguity preserves
        # delivery order exactly, like build_shared_groups' run splice)
        if junction is not None and runtime_decline(qr) is None:
            run = []
            for r in reversed(junction.receivers):
                if (type(r) is QueryRuntime
                        and runtime_decline(r) is None
                        and getattr(r, "_fused_group", None) is None
                        and r._batch_cap == qr._batch_cap
                        and len(run) < group_cap()):
                    run.append(r)
                else:
                    break
            run.reverse()
            if len(run) >= 2:
                import time as _time
                self._splice_seq += 1
                gname = (f"shared:{junction.definition.id}:"
                         f"live{self._splice_seq}")
                t0 = _time.perf_counter_ns()
                try:
                    g = SharedStepGroup(gname, run, junction)
                    g.warmup((g._batch_cap,))
                except Exception as e:  # noqa: BLE001
                    for m in run:
                        m._fused_group = None
                    self._splice_failed(f"form {gname}: {e}")
                    return {"fused": False, "failed": str(e)}
                ms = (_time.perf_counter_ns() - t0) / 1e6
                first = run[0]
                out = []
                for r in junction.receivers:
                    if r is first:
                        out.append(g)
                    elif any(r is m for m in run):
                        continue
                    else:
                        out.append(r)
                junction.receivers[:] = out
                self.shared_groups.append(g)
                self._track_splice("in", ms)
                self._refresh_optimizer_report()
                return {"fused": True, "group": gname, "retrace_ms": ms}
            reason = reason or SPLICE_DECLINE_NO_GROUP
        self._track_splice("declined")
        rep = self.optimizer_report
        rep.setdefault("splice_declined", {})[qr.name] = reason
        return {"fused": False, "declined": reason}

    def _unfuse_query(self, qr, *, keep: bool) -> None:
        """Take `qr` out of its fused group: splice-out when the group
        survives (>2 members), else dissolve the pair back to standalone
        receivers in their original slot. keep=True re-subscribes `qr`
        standalone (the quota-divert path); keep=False drops it (detach).
        A failed splice-out falls back LOUDLY to dissolving the whole
        group — the old full-rebuild path."""
        group = qr._fused_group
        junction = group.junction
        if len(group.members) > 2:
            try:
                ms = group.splice_out(qr)
                self._track_splice("out", ms)
                if keep:
                    junction.subscribe(qr)
                self._refresh_optimizer_report()
                return
            except Exception as e:  # noqa: BLE001 — group rolled back
                self._splice_failed(f"splice_out {qr.name}: {e}")
        members = group.dissolve()
        survivors = [m for m in members if m is not qr or keep]
        out = []
        for r in junction.receivers:
            if r is group:
                out.extend(survivors)
            else:
                out.append(r)
        junction.receivers[:] = out
        self.shared_groups[:] = [g for g in self.shared_groups
                                 if g is not group]
        self._track_splice("out")
        self._refresh_optimizer_report()

    def _refresh_optimizer_report(self) -> None:
        rep = self.optimizer_report
        if rep is None:
            return
        rep["groups"] = len(self.shared_groups)
        rep["queries_fused"] = sum(len(g.members)
                                   for g in self.shared_groups)
        rep["group_members"] = {g.name: [m.name for m in g.members]
                                for g in self.shared_groups}

    def _track_splice(self, kind: str, ms: Optional[float] = None) -> None:
        self.ctx.statistics.track_splice(kind, ms)
        tele = self.ctx.telemetry
        if tele is not None:
            tele.record_splice(kind, ms)

    def _splice_failed(self, reason: str) -> None:
        import logging
        logging.getLogger("siddhi_tpu").warning(
            "splice failed, falling back to standalone dispatch: %s",
            reason)
        self._track_splice("failed")
        rec = self.ctx.recorder
        if rec is not None:
            rec.trigger("splice_failure", reason=reason)

    # -------------------------------------------------- tenant enforcement

    def _enforce_tenant_quotas(self) -> None:
        """Flush-boundary device-time quota enforcement (NEVER inside
        junction dispatch — _deliver iterates receivers directly). An
        over-budget tenant's queries are spliced out of their groups and
        force-trip quota breakers, so the junction diverts their batches
        (dead-letter path, replayable) while siblings run untouched. Once
        the rolling window drains under budget the breakers lift and the
        queries re-splice automatically."""
        tenants = self.tenants
        if tenants is None:
            return
        over = set(tenants.over_budget())
        rec = self.ctx.recorder
        for tid in tenants.ids():
            if tid in over:
                if tenants.note_breach(tid):
                    self.ctx.statistics.track_tenant_breach(tid)
                    dom = tenants.dominant_query(tid) or "?"
                    if rec is not None:
                        rec.trigger(
                            "tenant_quota_breach",
                            reason=f"tenant {tid!r} over device.ms budget "
                                   f"(dominant query {dom!r})")
                quota = tenants.quota(tid)
                from .breaker import CircuitBreaker
                for qname in tenants.queries_of(tid):
                    qr = self.query_runtimes.get(qname)
                    if qr is None:
                        continue
                    br = getattr(qr, "breaker", None)
                    if br is not None and getattr(br, "quota_tenant",
                                                  None) is None:
                        continue  # user-declared breaker: never touched
                    if getattr(qr, "_fused_group", None) is not None:
                        self._unfuse_query(qr, keep=True)
                    if br is None:
                        br = CircuitBreaker(
                            threshold=1, window_s=quota.window_s,
                            cooldown_s=quota.window_s, owner=qname)
                        br.quota_tenant = tid
                        qr.breaker = br
                    if br.state != "open":
                        br.record_failure()  # (re-)trip: divert until lift
            elif tenants.diverting(tid):
                tenants.note_recovery(tid)
                for qname in tenants.queries_of(tid):
                    qr = self.query_runtimes.get(qname)
                    if qr is None:
                        continue
                    br = getattr(qr, "breaker", None)
                    if br is not None and getattr(br, "quota_tenant",
                                                  None) == tid:
                        qr.breaker = None
                        self._try_splice_in(qr)

    # ---------------------------------------------------------------- control

    def start(self, *, connect_sources: bool = True,
              start_persist_scheduler: bool = True) -> None:
        """Start the runtime. The blue-green upgrade path starts the v2
        runtime in SHADOW (`connect_sources=False`,
        `start_persist_scheduler=False`): fully built and able to process,
        but not yet pulling from transports and not yet writing revisions —
        cutover calls connect_sources()/_start_persist_scheduler() after the
        swap commits."""
        self._started = True
        from ..telemetry.profiling import maybe_start_jax_profiler
        # SIDDHI_PROFILE=<dir>: the first runtime to start owns the
        # process-wide jax.profiler capture and closes it on shutdown
        self._owns_jax_trace = maybe_start_jax_profiler()
        if self.aot_warmup:
            self.warmup()
        if self.ctx.async_callbacks and self.ctx.decoder is None:
            from .stream import AsyncDecoder
            self.ctx.decoder = AsyncDecoder()
        for j in self.junctions.values():
            j.start_async()
        for sink in self.sinks:
            sink.connect()
        if connect_sources:
            self.connect_sources()
        if self.triggers:
            now = self.ctx.timestamp_generator.current_time()
            for tr in self.triggers.values():
                tr.start(now)
            self.flush(now)
        if self.auto_flush_ms:
            import threading
            # producers must pair their staged appends under the controller
            # lock once a flusher thread can swap the lists concurrently
            self.ctx.autoflush_active = True
            self._flusher_stop = threading.Event()
            self._flusher_thread = threading.Thread(
                target=self._flusher_loop, daemon=True,
                name=f"siddhi-flusher-{self.app.name}")
            self._flusher_thread.start()
        if start_persist_scheduler:
            self._start_persist_scheduler()
        if self.slo_engine is not None and self._slo_thread is None:
            import threading
            self._slo_stop = threading.Event()
            self._slo_thread = threading.Thread(
                target=self._slo_loop, daemon=True,
                name=f"siddhi-slo-{self.app.name}")
            self._slo_thread.start()

    def _slo_loop(self) -> None:
        """Daemon: one SLO evaluation pass per engine interval (~1 s).
        tick() samples every objective's cumulative reader, re-judges both
        burn windows, and fires the recorder on fresh breaches; a failing
        tick is logged and retried — objectives must not die with one bad
        sample."""
        import logging
        eng = self.slo_engine
        while not self._slo_stop.wait(eng.interval_s):
            if not self._started:
                return
            try:
                eng.tick()
            except Exception:  # noqa: BLE001 — evaluator must not die
                logging.getLogger("siddhi_tpu").exception(
                    "SLO evaluation tick failed (will retry next interval)")

    def diagnostics(self, reason: str = "manual") -> dict:
        """Force a diagnostic bundle now (POST /siddhi-apps/<name>/
        diagnostics). Bypasses the recorder's de-dup/rate-limit gates."""
        rec = self.ctx.recorder
        path = rec.trigger("manual", reason=reason, force=True)
        return {"bundle": path, "recorder": rec.report()}

    def connect_sources(self) -> None:
        """Connect every declared source transport (idempotent — already
        connected sources no-op in their connect paths)."""
        for source in self.sources:
            source.connect_with_retry()

    def _start_persist_scheduler(self) -> None:
        if not (self.persistence_interval_s
                and self.persistence_store is not None) \
                or self._persist_thread is not None:
            return
        import threading
        self._persist_stop = threading.Event()
        self._persist_thread = threading.Thread(
            target=self._persist_loop, daemon=True,
            name=f"siddhi-persist-{self.app.name}")
        self._persist_thread.start()

    def _persist_loop(self) -> None:
        """Daemon: bound data-at-risk to ~persistence_interval_s without the
        caller ever invoking persist() (reference: the operator-driven
        SiddhiManager.persist on a cron; here it is built in). A failed
        persist is logged and retried next tick — the WAL still covers the
        window."""
        import logging
        interval = float(self.persistence_interval_s)
        while not self._persist_stop.wait(interval):
            if not self._started:
                return
            if self._recovering:  # recover() owns the journal right now
                continue
            try:
                self.persist()
            except Exception:  # noqa: BLE001 — scheduler must not die
                logging.getLogger("siddhi_tpu").exception(
                    "periodic persist failed (will retry next interval)")

    def _flusher_loop(self) -> None:
        """Daemon: bound staged-row latency to ~auto_flush_ms without the
        caller polling flush() (the Disruptor's immediate consumption).
        Also drives heartbeats for time-semantic queries in realtime mode
        so absences/time windows fire on wall clock during idle."""
        interval = self.auto_flush_ms / 1000.0

        def _needs_hb() -> bool:
            return any(
                getattr(qr, "has_time_semantics", False)
                for qr in self.query_runtimes.values()) or any(
                w.has_time_semantics for w in self.windows.values())

        epoch = self._plan_epoch
        needs_hb = _needs_hb()
        while not self._flusher_stop.wait(interval / 2):
            if not self._started:
                return
            if self._plan_epoch != epoch:
                # attach/detach changed the plan shape: recompute whether
                # any live query still needs wall-clock heartbeats
                epoch = self._plan_epoch
                needs_hb = _needs_hb()
            try:
                # async junctions drain via their own feeder threads;
                # the flusher covers synchronous staging. The whole tick
                # runs under the controller lock: query steps donate their
                # state buffers, so a tick racing a user-thread delivery
                # into the same runtime would double-donate
                with self.ctx.controller_lock:
                    staged = any(j._staged_rows or j._tap_queue
                                 for j in self.junctions.values())
                    if staged:
                        self.flush()
                    elif needs_hb and not self.ctx.playback:
                        self.heartbeat()
            except Exception:  # noqa: BLE001 — flusher must not die
                import logging
                logging.getLogger("siddhi_tpu").exception(
                    "auto-flush tick failed")

    def warmup(self, buckets=None) -> WarmupResult:
        """AOT-compile every query runtime's jitted step for its lane-bucket
        ladder (shape-bucketed queries: min_bucket..batch_size; shape-baked
        ones: the single full capacity), so steady-state traffic — and
        benchmark measurement windows — never absorb first-compile latency.
        Live state is untouched (see query_runtime.aot_warm). Returns
        {query_name: fresh_compile_count}; a step that does not compile is
        logged at ERROR and handed back in the result's `failures`
        ({query_name: exception}) — a server keeps starting, a caller that
        needs every step compiled (chip_smoke.py, the benchmark) treats any
        entry as fatal instead of meeting the same error later on a feeder
        thread."""
        import logging
        out = WarmupResult()
        with self.ctx.controller_lock:
            for name, qr in self.query_runtimes.items():
                if getattr(qr, "_fused_group", None) is not None:
                    continue  # its step never runs: the group's fused jit does
                fn = getattr(qr, "warmup", None)
                if fn is None:
                    continue
                try:
                    out[name] = fn(buckets)
                except Exception as e:  # noqa: BLE001 — returned to caller
                    out.failures[name] = e
                    logging.getLogger("siddhi_tpu").exception(
                        "AOT warmup failed for query %r", name)
            for g in self.shared_groups:
                try:
                    out[g.name] = g.warmup(buckets)
                except Exception as e:  # noqa: BLE001 — returned to caller
                    out.failures[g.name] = e
                    logging.getLogger("siddhi_tpu").exception(
                        "AOT warmup failed for shared group %r", g.name)
        return out

    def shutdown(self, *, flush_durable: bool = True,
                 drain: bool = True) -> None:
        self._started = False
        if self._persist_stop is not None:
            self._persist_stop.set()
            if self._persist_thread is not None:
                self._persist_thread.join(timeout=10)
            self._persist_stop = self._persist_thread = None
        if self._slo_stop is not None:
            self._slo_stop.set()
            if self._slo_thread is not None:
                self._slo_thread.join(timeout=5)
            self._slo_stop = self._slo_thread = None
        if self._flusher_stop is not None:
            self._flusher_stop.set()
            if self._flusher_thread is not None:
                self._flusher_thread.join(timeout=5)
            self._flusher_stop = None
            # producers pair staged appends under the controller lock only
            # while a flusher can swap the lists — post-shutdown send()s
            # must not keep taking it for a flusher that is gone
            self.ctx.autoflush_active = False
        # rows accepted by send() must not vanish silently on stop: drain
        # the pre-staging/staging buffers through the pipeline; whatever a
        # failing drain leaves behind is counted and reported, not dropped
        # on the floor unrecorded
        def _staged() -> int:
            return sum(len(j._staged_rows) + len(j._tap_queue)
                       for j in self.junctions.values())
        n0, drain_failed = _staged(), False
        if drain and n0:
            import logging
            try:
                self.drain()
            except Exception:  # noqa: BLE001 — shutdown must complete
                drain_failed = True
                logging.getLogger("siddhi_tpu").exception(
                    "draining staged rows at shutdown failed")
        remaining = _staged()
        if drain_failed:
            # flush() swaps the staged lists before delivering, so rows that
            # died mid-drain are no longer countable — report the pre-drain
            # depth as the (upper-bound) loss instead of pretending zero
            remaining = max(remaining, n0)
        if remaining:
            import logging
            self.ctx.statistics.track_shutdown_discard(remaining)
            logging.getLogger("siddhi_tpu").warning(
                "shutdown discarded %d staged row(s) (see statistics "
                "recovery.shutdown_discarded)", remaining)
        if drain and self.ctx.event_time is not None:
            # rows the event-time gates still hold are REAL accepted events:
            # deliver them (watermark jumps to max seen) rather than letting
            # shutdown silently eat the tail of every pane
            import logging
            try:
                self.release_watermarks()
            except Exception:  # noqa: BLE001 — shutdown must complete
                logging.getLogger("siddhi_tpu").exception(
                    "releasing event-time watermarks at shutdown failed")
        for j in self.junctions.values():
            j.stop_async()
        if self.ctx.decoder is not None:
            decoder, self.ctx.decoder = self.ctx.decoder, None
            try:
                decoder.stop()
            except SiddhiAppRuntimeError:  # shutdown must complete
                import logging
                logging.getLogger("siddhi_tpu").exception(
                    "async callbacks did not drain at shutdown")
        for a in self.aggregations.values():
            if flush_durable:
                a.flush_durable()  # durable duration tables (restart rebuild)
            a.close_durable()
        for t in self.tables.values():
            if hasattr(t, "shutdown"):
                t.shutdown()
        for tr in self.triggers.values():
            tr.shutdown()
        for source in self.sources:
            source.disconnect()
        for sink in self.sinks:
            sink.disconnect()
        if self.wal is not None:
            self.wal.close()
        if self._owns_jax_trace:
            from ..telemetry.profiling import stop_jax_profiler
            stop_jax_profiler()
            self._owns_jax_trace = False
        if self.ctx.recorder is not None:
            self.ctx.recorder.close()  # detach the log-tail handler

    # ------------------------------------------------------------------- I/O

    def get_input_handler(self, stream_id: str) -> InputHandler:
        if stream_id not in self.input_handlers:
            junction = self.junctions.get(stream_id)
            if junction is None:
                raise DefinitionNotExistError(f"stream {stream_id!r} is not defined")
            self.input_handlers[stream_id] = InputHandler(junction)
        return self.input_handlers[stream_id]

    def add_callback(self, stream_id: str, callback,
                     columnar: bool = False) -> None:
        """Subscribe to a stream. `columnar=True` delivers ColumnarBlock
        batches (compacted numpy columns, lazy string decode) instead of
        materialized Event lists — the high-throughput form of the
        reference's Event[] callback (StreamCallback.java:38)."""
        from .stream import BatchStreamCallback, FunctionBatchCallback
        if stream_id.startswith("!"):
            junction = self.fault_junctions.get(stream_id[1:])
        else:
            junction = self.junctions.get(stream_id)
        if junction is None:
            raise DefinitionNotExistError(f"stream {stream_id!r} is not defined")
        if columnar and not isinstance(
                callback, (BatchStreamCallback, StreamCallback)):
            callback = FunctionBatchCallback(callback)
        elif not isinstance(callback, (StreamCallback, BatchStreamCallback)):
            callback = FunctionStreamCallback(callback)
        junction.subscribe(callback)

    def add_query_callback(self, query_name: str, callback) -> None:
        qr = self.query_runtimes.get(query_name)
        if qr is None:
            raise DefinitionNotExistError(f"query {query_name!r} is not defined")
        if not isinstance(callback, QueryCallback):
            callback = FunctionQueryCallback(callback)
        qr.add_callback(callback)

    def query(self, on_demand_text: str, now: Optional[int] = None):
        """Execute an on-demand (pull) query against a table (reference:
        SiddhiAppRuntimeImpl.query:309-371). Returns a list of Events."""
        from .. import compiler
        from .ondemand import OnDemandQueryRuntime

        if not hasattr(self, "_ondemand_cache"):
            self._ondemand_cache = {}
        rt = self._ondemand_cache.get(on_demand_text)
        if rt is None:
            odq = compiler.parse_on_demand_query(on_demand_text)
            from ..query_api.execution import OutputAction as _OA
            if odq.action != _OA.RETURN:
                rt = self._build_crud_runtime(odq)
                self._ondemand_cache[on_demand_text] = rt
                self.flush()
                t = (now if now is not None
                     else self.ctx.timestamp_generator.current_time())
                return rt.execute(t)
            store = self.tables.get(odq.input_store_id)
            if store is None:
                store = self.windows.get(odq.input_store_id)
            if store is None and odq.input_store_id in self.aggregations:
                # aggregation store query: bind `per`/`within` into a view
                # (reference: AggregationRuntime.find, within/per clauses)
                import dataclasses as dc
                agg = self.aggregations[odq.input_store_id]
                if odq.per is None:
                    raise SiddhiAppCreationError(
                        f"aggregation {odq.input_store_id!r} queries need "
                        "`per '<duration>'`")
                store = agg.view(odq.per, odq.within_range)
                odq = dc.replace(odq, per=None, within_range=None)
            if store is None:
                raise DefinitionNotExistError(
                    f"store {odq.input_store_id!r} is not defined")
            rt = OnDemandQueryRuntime(odq, store, self.ctx, self.ctx.registry)
            self._ondemand_cache[on_demand_text] = rt
        self.flush()
        t = now if now is not None else self.ctx.timestamp_generator.current_time()
        return rt.execute(t)

    def _build_crud_runtime(self, odq):
        """Write-form on-demand queries (delete/update/update-or-insert/
        select-insert) — reference: OnDemandQueryParser non-find runtimes."""
        from ..io.record_table import RecordCrudRuntime, RecordTableRuntime
        from ..query_api.execution import OutputAction as _OA
        from .ondemand import OnDemandCrudRuntime
        target = self.tables.get(odq.target_id)
        if target is None:
            raise DefinitionNotExistError(
                f"table {odq.target_id!r} is not defined")
        if isinstance(target, RecordTableRuntime):
            source = None
            if odq.action == _OA.INSERT and odq.input_store_id is not None:
                source = self.tables.get(odq.input_store_id)
                if source is None:
                    source = self.windows.get(odq.input_store_id)
                if source is None:
                    raise DefinitionNotExistError(
                        f"store {odq.input_store_id!r} is not defined")
            return RecordCrudRuntime(odq, target, self.ctx,
                                     self.ctx.registry, source_store=source)
        source = None
        if odq.action == _OA.INSERT and odq.input_store_id is not None:
            source = self.tables.get(odq.input_store_id)
            if source is None:  # NOT `or`: an empty table is falsy (__len__)
                source = self.windows.get(odq.input_store_id)
            if source is None and odq.input_store_id in self.aggregations:
                raise SiddhiAppCreationError(
                    "insert-into from aggregations: query the aggregation "
                    "and insert host-side instead")
            if source is None:
                raise DefinitionNotExistError(
                    f"store {odq.input_store_id!r} is not defined")
        return OnDemandCrudRuntime(odq, target, self.ctx, self.ctx.registry,
                                   source_store=source)

    def flush(self, now: Optional[int] = None) -> None:
        """Drive every staged batch through the pipeline (source junctions
        first; device-to-device chaining cascades synchronously)."""
        if self.triggers:
            t = now if now is not None else self.ctx.timestamp_generator.current_time()
            for tr in self.triggers.values():
                tr.poll(t)
        for j in list(self.junctions.values()):
            j.flush(now)
        # tenant device-time quotas enforce at this boundary — never inside
        # junction dispatch, where receiver lists must not be mutated
        self._enforce_tenant_quotas()

    def drain(self, timeout: float = 120.0) -> None:
        """Flush staged rows AND block until every async callback has fired.
        The barrier for async_callbacks=True mode (with synchronous
        callbacks this is equivalent to flush()). Raises
        SiddhiAppRuntimeError if the callbacks have not all fired within
        `timeout` seconds or a decoder thread died (AsyncDecoder.drain)."""
        self.flush()
        if self.ctx.decoder is not None:
            self.ctx.decoder.drain(timeout)

    def release_watermarks(self, now: Optional[int] = None) -> None:
        """End-of-stream drain for @app:eventTime: force every gate's
        watermark to its max seen event time and deliver the held rows in
        event-time order. Stragglers sent afterwards classify as late
        (replayable), never as out-of-order emissions."""
        for j in self.junctions.values():
            if j._et is not None:
                j.release_event_time(now)
        self.flush(now)

    def heartbeat(self, now: Optional[int] = None) -> None:
        """Advance watermarks: flush + deliver empty timer batches to queries
        with time-driven windows (the reference Scheduler's TIMER events).
        In playback mode a bare heartbeat() bumps the virtual clock by the
        @app:playback increment (idle-time heartbeat,
        TimestampGeneratorImpl.java:92-131)."""
        tg = self.ctx.timestamp_generator
        if now is None and tg.playback and tg.playback_increment_ms:
            t = tg.advance_idle()
        else:
            t = now if now is not None else tg.current_time()
        self.flush(t)
        for w in self.windows.values():
            if w.has_time_semantics:
                w.heartbeat(t)
        for a in self.aggregations.values():
            a._maybe_evict(t)  # retention purge rides the heartbeat clock
        for pr in self.partitions.values():
            if pr.has_time_semantics or pr._purge_idle_ms is not None:
                pr.heartbeat(t)
        seen: set[int] = set()
        for qr in self.query_runtimes.values():
            if not qr.has_time_semantics or getattr(qr, "_partitioned", False):
                continue
            if hasattr(qr, "heartbeat"):  # pattern runtimes drive themselves
                qr.heartbeat(t)
                continue
            j = getattr(qr, "input_junction", None)
            if j is not None and id(j) not in seen:
                seen.add(id(j))
                j.heartbeat(t)
        for j in self.junctions.values():
            # event-time gates ride the heartbeat too (idle.timeout release)
            # even when no consumer has time semantics
            if j._et is not None and id(j) not in seen:
                seen.add(id(j))
                j.heartbeat(t)
        # overflow counters warn from the heartbeat too, not only when the
        # user polls statistics_report()
        self.collect_overflow()

    # ----------------------------------------------------- persist / restore

    @property
    def persistence_store(self):
        return getattr(self, "_persistence_store", None)

    @persistence_store.setter
    def persistence_store(self, store) -> None:
        self._persistence_store = store

    def _snapshot_service(self):
        from ..state.persistence import SnapshotService
        if not hasattr(self, "_snap_service"):
            self._snap_service = SnapshotService(self)
        return self._snap_service

    def snapshot(self) -> bytes:
        """Full state snapshot as bytes (reference:
        SiddhiAppRuntimeImpl.snapshot)."""
        return self._snapshot_service().full_snapshot()

    def restore(self, snapshot: bytes, *, elements=None) -> None:
        """Restore a snapshot. `elements` (section -> element-name set)
        limits the restore to the migratable subset during a state-mapped
        upgrade (state/persistence.py SnapshotService.restore)."""
        self._snapshot_service().restore(snapshot, elements=elements)

    def persist(self) -> str:
        """Snapshot to the configured PersistenceStore; returns the revision
        (reference: SiddhiAppRuntimeImpl.persist:686)."""
        from ..errors import NoPersistenceStoreError
        store = self.persistence_store
        if store is None:
            raise NoPersistenceStoreError(
                "no persistence store configured "
                "(set manager.persistence_store)")
        import time as _time
        ms = int(_time.time() * 1000)
        # strictly increasing: two persists in one millisecond must not
        # collide (delta persistence chains rely on revision uniqueness/order)
        last = getattr(self, "_last_rev_ms", 0)
        ms = max(ms, last + 1)
        self._last_rev_ms = ms
        revision = f"{ms}_{self.app.name}"
        # snapshot→save→rotate is ONE critical section under the controller
        # lock (the reference's world-stopping ThreadBarrier): WAL-journaled
        # sends take the same lock, so every journaled row is either flushed
        # into this snapshot (its record is safely rotated away) or staged
        # after the rotation (its record lands in the new segment) — never
        # journaled-then-lost in between
        with self.ctx.controller_lock:
            store.save(self.app.name, revision, self.snapshot())
            for a in self.aggregations.values():
                a.flush_durable()  # write-through durable duration tables
            if self.wal is not None:
                # rotate AFTER the store accepted the snapshot
                # (save-then-rotate: a crash between the two duplicates the
                # suffix on recover, never loses it)
                self.wal.rotate(revision)
        return revision

    def restore_revision(self, revision: str) -> None:
        from ..errors import CannotRestoreStateError, NoPersistenceStoreError
        store = self.persistence_store
        if store is None:
            raise NoPersistenceStoreError("no persistence store configured")
        blob = store.load(self.app.name, revision)
        if blob is None:
            raise CannotRestoreStateError(f"revision {revision!r} not found")
        self.restore(blob)

    def restore_last_revision(self) -> Optional[str]:
        """Reference: SiddhiAppRuntimeImpl.restoreLastRevision."""
        store = self.persistence_store
        if store is None:
            from ..errors import NoPersistenceStoreError
            raise NoPersistenceStoreError("no persistence store configured")
        rev = store.get_last_revision(self.app.name)
        if rev is not None:
            self.restore_revision(rev)
        return rev

    def recover(self) -> dict:
        """Crash recovery: restore the last persisted revision (when a
        persistence store is configured) then replay the write-ahead journal
        with the events' original timestamps — at-least-once restart
        semantics. Safe on a clean state too (no revision, empty WAL = a
        no-op). Returns {"revision", "wal_replayed"}; counts surface in
        statistics_report()["recovery"]."""
        rev = None
        self._recovering = True  # the periodic persist scheduler stands down
        try:
            if self.persistence_store is not None:
                rev = self.restore_last_revision()
            replayed = 0
            if self.wal is not None:
                replayed = self.wal.replay(self)
            self.flush()
        finally:
            self._recovering = False
        self.ctx.statistics.track_recovery(replayed)
        if self.ctx.recorder is not None:
            # recovery is an anomaly worth evidence: capture the post-replay
            # state (WAL position, replayed count, stats) for later triage
            self.ctx.recorder.trigger(
                "recovery", reason=f"revision={rev} wal_replayed={replayed}")
        return {"revision": rev, "wal_replayed": replayed}

    # ------------------------------------------------------------------ health

    def health(self) -> dict:
        """Readiness view of one app (served by `/ready` in service.py):
        overall state (running | degraded | recovering | stopped — degraded
        = at least one circuit breaker not closed), per-query breaker
        snapshots, and staged-queue depth vs. capacity for every bounded
        junction (with its backpressure-paused flag)."""
        breakers = {}
        degraded = False
        for name, qr in self.query_runtimes.items():
            br = getattr(qr, "breaker", None)
            if br is None:
                continue
            breakers[name] = br.snapshot()
            if br.state != "closed":
                degraded = True
        queues = {}
        for sid, j in self.junctions.items():
            if j.capacity is None:
                continue
            depth = j._staged_depth()
            queues[sid] = {"depth": depth, "capacity": j.capacity,
                           "paused": j._bp_paused}
        if self._recovering:
            state = "recovering"
        elif not self._started:
            state = "stopped"
        elif degraded:
            state = "degraded"
        else:
            state = "running"
        return {"state": state, "breakers": breakers, "queues": queues}

    # -------------------------------------------------------------- statistics

    @property
    def statistics(self) -> Statistics:
        return self.ctx.statistics

    def set_statistics_level(self, level: str) -> None:
        """Runtime-switchable metric level (reference:
        SiddhiAppRuntimeImpl.setStatisticsLevel:868)."""
        self.ctx.statistics.set_level(level)

    def statistics_report(self) -> dict:
        return self.ctx.statistics.report(runtime=self)

    @property
    def cost_report(self) -> dict:
        """Static cost prediction for this app (analysis/cost.py), computed
        lazily under the runtime's effective batch/group capacities and
        cached — statistics_report()['cost'] pairs it with live telemetry."""
        rep = getattr(self, "_cost_report", None)
        if rep is None:
            from ..analysis.cost import compute_cost
            rep = compute_cost(self.app,
                               batch_size=self.ctx.batch_size,
                               group_capacity=self.ctx.group_capacity
                               ).to_dict()
            self._cost_report = rep
        return rep

    def collect_overflow(self, report: bool = False) -> None:
        """Sweep every runtime's device state for capacity-overflow counters
        and surface them via Statistics.record_overflow (one-shot warning
        per counter). Syncs a handful of scalars — called from
        statistics_report() (`report`: high waters start anew) and the
        heartbeat, not the hot path.

        Counters: window-ring overwrites of live rows (SlidingState /
        expression windows), key-table unresolved lanes (group-by, distinct
        pairs, aggregation buckets), pattern pending-table drops, keyed
        session key-capacity drops, join pair-block/candidate-walk drops."""
        import numpy as np

        from ..ops.aggregators import HLLState
        from ..ops.groupby import KeyTable
        from ..ops.keyed_window import KeyedWindowState
        from ..ops.ratelimit import WindowedSnapshotState
        from ..ops.windows import SlidingState
        from ..ops.windows_extra import KeyedSessionState
        from .join_runtime import JoinQueryRuntime
        from .pattern_runtime import PatternQueryRuntime, PatternState

        stats = self.ctx.statistics

        def scan(label: str, obj, acc: dict) -> None:
            # accumulate DEVICE scalars; the single device_get below fetches
            # everything in one round trip (a per-counter np.asarray is a
            # blocking device sync EACH — see event.to_host_events)
            def add(key, arr):
                acc.setdefault(key, []).append(arr)

            if isinstance(obj, KeyTable):
                add("key_table_unresolved", obj.misses)
            elif isinstance(obj, SlidingState):
                add("window_ring_overflow", obj.overflow)
                add("window_expiry_deferred", obj.deferred)
            elif isinstance(obj, KeyedSessionState):
                add("session_key_dropped", obj.dropped)
            elif isinstance(obj, PatternState):
                add("pattern_pending_dropped", obj.dropped)
            elif isinstance(obj, KeyedWindowState):
                add("partition_keys_dropped", obj.dropped)
            elif isinstance(obj, WindowedSnapshotState):
                add("snapshot_ring_overflow", obj.overflow)
            elif isinstance(obj, HLLState):
                add("hll_groups_dropped", obj.dropped)
            import dataclasses as _dc
            if isinstance(obj, dict):
                for v in obj.values():
                    scan(label, v, acc)
            elif hasattr(obj, "_fields"):  # NamedTuple: recurse into fields
                for f in obj._fields:
                    scan(label, getattr(obj, f), acc)
            elif isinstance(obj, (tuple, list)):
                for v in obj:
                    scan(label, v, acc)
            elif _dc.is_dataclass(obj) and not isinstance(obj, type):
                for f in _dc.fields(obj):  # e.g. SelectorState
                    scan(label, getattr(obj, f.name), acc)

        import jax
        import jax.numpy as jnp
        pending: dict[str, list] = {}
        # under the controller lock, and copied there: join, pattern and
        # aggregation steps DONATE their state, so a counter read beside a
        # running feeder is deleted before the fetch below reaches it
        # ("Array has been deleted"). The copies are a few scalar ops; the
        # fetch, which waits for the device, runs outside the lock
        with self.ctx.controller_lock:
            sources: list[tuple[str, object]] = []
            sources += [(f"query:{n}", qr.state)
                        for n, qr in self.query_runtimes.items()
                        if hasattr(qr, "state")]
            sources += [(f"window:{n}", w.state)
                        for n, w in self.windows.items()]
            sources += [(f"aggregation:{n}", a.state)
                        for n, a in self.aggregations.items()]
            for label, obj in sources:
                acc: dict = {}
                scan(label, obj, acc)
                for k, arrs in acc.items():
                    pending[f"{label}.{k}"] = arrs
            joins = {f"query:{n}.join_pairs_dropped": qr
                     for n, qr in self.query_runtimes.items()
                     if isinstance(qr, JoinQueryRuntime)
                     and qr._dropped_dev is not None}
            for key, qr in joins.items():
                pending[key] = [qr._dropped_dev]
            pending = jax.tree_util.tree_map(jnp.copy, pending)
            # pattern tables and sliding windows: the account each keeps
            # in its state (high waters start anew at a report)
            patterns = {n: qr.device_counters(report)
                        for n, qr in self.query_runtimes.items()
                        if isinstance(qr, PatternQueryRuntime)
                        or (isinstance(qr, QueryRuntime)
                            and qr.cells is not None)}
            keyed = {n: pr.keyed.device_counters()
                     for n, pr in self.partitions.items()
                     if pr.keyed is not None}
            tables = {n: t.device_counters() for n, t in self.tables.items()
                      if getattr(t, "index_key", None) is not None}
        # ONE device->host round trip
        fetched, counted, keyed, tables = jax.device_get(
            (pending, patterns, keyed, tables))
        for name, arrs in fetched.items():
            stats.record_overflow(name, int(sum(np.sum(a) for a in arrs)))
        for key, qr in joins.items():
            qr.dropped_synced = int(fetched[key][0])
        for n, values in counted.items():
            self.query_runtimes[n].sync_counters(values)
        for n, values in keyed.items():
            self.partitions[n].keyed.sync_counters(values)
        for n, values in tables.items():
            self.tables[n].sync_counters(values)
            stats.record_overflow(f"table:{n}.table_rows_dropped",
                                  self.tables[n].synced["dropped"])

    # ---------------------------------------------------------------- debugger

    def debug(self):
        """Attach a debugger (reference: SiddhiAppRuntimeImpl.debug():666 →
        core/debugger/SiddhiDebugger.java:36)."""
        from .debugger import SiddhiDebugger
        if getattr(self.ctx, "debugger", None) is None:
            self.ctx.debugger = SiddhiDebugger(self)
        if not self._started:
            self.start()
        return self.ctx.debugger


class _TableJunctionAdapter:
    """Adapts the query-output junction interface onto a table insert."""

    def __init__(self, table) -> None:
        self.table = table

    def publish_batch(self, batch, now) -> None:
        self.table.insert_batch(batch)
