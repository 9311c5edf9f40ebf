"""Query planner & runtime — AST query → one jitted step function.

Reference counterpart: core/util/parser/QueryParser.java:70 builds a chain of
Processor objects walked per event (ProcessStreamReceiver → FilterProcessor →
WindowProcessor → QuerySelector → OutputRateLimiter → OutputCallback,
call stack SURVEY §3.2). The TPU build collapses that chain into ONE pure
function per query:

    step(state, batch, now) -> (state', out_batch)

traced once and jit-compiled; filters become masks, the window emits a typed
chunk, the selector runs grouped scans — all fused by XLA into a handful of
kernels per micro-batch. State is a pytree (window rings + group tables),
donated on each call so device buffers are reused in place.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..errors import SiddhiAppCreationError
from ..extension.registry import ExtensionKind, Registry
from ..ops.expr_compile import Scope, TypeResolver, compile_expression
from ..ops.selector import CompiledSelector
from ..ops.window_factories import make_window
from ..telemetry.tracing import StageCells, stage
from ..ops.windows import PassThroughWindow, WindowOp
from ..query_api.definition import AttributeType, StreamDefinition, Attribute
from ..query_api.execution import (
    OutputAction,
    OutputEventType,
    Query,
    SingleInputStream,
)
from ..query_api.expression import Expression, Variable
from . import dtypes
from .context import SiddhiAppContext
from .event import Event, EventBatch, EventType, StreamCodec
from .stream import Receiver, StreamJunction


class QueryCallback:
    """Reference: core/query/output/callback/QueryCallback.java:37 — receives
    (timestamp, inEvents, removeEvents) per emission chunk."""

    def receive(self, timestamp: int, in_events, remove_events) -> None:
        raise NotImplementedError


class FunctionQueryCallback(QueryCallback):
    def __init__(self, fn):
        self.fn = fn

    def receive(self, timestamp: int, in_events, remove_events) -> None:
        self.fn(timestamp, in_events, remove_events)


@dataclass
class QueryPlanInputs:
    definition: StreamDefinition
    codec: StreamCodec
    frame_ref: str


def aot_warm(jit_fn, *args) -> None:
    """Populate `jit_fn`'s dispatch cache for `args`' shape signature
    WITHOUT executing it — jax shares `lower().compile()` executables
    with the normal call path, so the next real call is a pure cache hit.
    Warmup therefore has no step side effects, cannot touch live state,
    and never runs a host callback (CronWindow's, ops/windows_extra.py:
    executing a step during warmup can deadlock jax's CPU pure_callback
    path on small hosts)."""
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        args)
    jit_fn.lower(*abstract).compile()


def selector_round_width(window, selector, extras: dict,
                         lanes: int) -> Optional[int]:
    """The lanes a round of the selector takes over the window's chunk of
    `lanes`, or None where it takes the whole chunk in one call. A sliding
    window whose expiry width exceeds its batch (`@capacity(expire=...)`)
    packs what left and what arrived at the front of a chunk mostly empty:
    a lane-sequential selector then runs in rounds of one batch over that
    prefix alone. A per-lane array among the scope's extras keeps the one
    call."""
    from ..ops.windows import SlidingWindow
    if not (isinstance(window, SlidingWindow) and window.E > window.B
            and selector.lane_sequential):
        return None
    if any(getattr(x, "shape", ())[:1] == (lanes,)
           for x in jax.tree_util.tree_leaves(extras)):
        return None
    return window.B


def _selects_aggregates(selector, registry) -> bool:
    """True if any select item contains an aggregator call — the same
    detection CompiledSelector performs, needed BEFORE the window is built
    (full-window snapshots change the window's expired-lane emission)."""
    from ..extension.registry import ExtensionKind
    from ..ops.aggregators import AggregatorFactory
    from ..query_api.expression import AttributeFunction, Expression

    def walk(e) -> bool:
        if isinstance(e, AttributeFunction):
            f = registry.lookup(ExtensionKind.AGGREGATOR, e.namespace, e.name)
            if isinstance(f, AggregatorFactory):
                return True
        for a in ("left", "right", "expression"):
            sub = getattr(e, a, None)
            if isinstance(sub, Expression) and walk(sub):
                return True
        return any(isinstance(p, Expression) and walk(p)
                   for p in getattr(e, "parameters", ()) or ())

    return any(walk(a.expression) for a in selector.attributes)


class QueryRuntime(Receiver):
    """Runtime for a single-input-stream query (joins/patterns have their own
    runtimes). Subscribes to the input junction; publishes to the output
    junction and/or query callbacks."""

    def __init__(
        self,
        query: Query,
        ctx: SiddhiAppContext,
        input_junction: StreamJunction,
        registry: Registry,
        name: Optional[str] = None,
        tables: Optional[dict] = None,
    ) -> None:
        assert isinstance(query.input_stream, SingleInputStream)
        self.query = query
        self.ctx = ctx
        self.name = name or query.name or f"query_{id(self)}"
        self.registry = registry
        self.input_junction = input_junction
        # per-query circuit breaker (@breaker(threshold=..., window=...,
        # cooldown=...)) — the input junction consults it around every
        # on_batch dispatch (core/breaker.py); None = failures propagate
        # per @OnError exactly as before
        from .breaker import breaker_from_annotations
        self.breaker = breaker_from_annotations(query, name=self.name)
        self.callbacks: list[QueryCallback] = []
        self.output_junction: Optional[StreamJunction] = None
        self.table_executor = None  # set by app runtime for table CRUD outputs
        self.tables = tables or {}
        # tables referenced by `in Table` conditions: their states become step
        # arguments (contents must not be baked into the trace as constants)
        self.dep_tables = sorted(
            tid for tid in _collect_in_sources(query) if tid in self.tables)
        # tables whose `in` conditions carry an index-eligible equality:
        # only these pay the (lazy) sorted-index rebuild per mutated batch
        self._index_tables = _collect_eq_probe_tables(query, self.tables)
        #: cached @store tables probed by `T.attr == <stream expr>` `in`
        #: conditions: once the store outgrows the cache, on_batch pre-warms
        #: the cache with the batch's probe values (store read-through,
        #: reference AbstractQueryableRecordTable.java:207-238). Populated
        #: after the resolver exists (see below).
        self._in_fallbacks: dict = {}

        in_stream = query.input_stream
        definition = input_junction.definition
        self.frame_ref = in_stream.reference_id
        self.codec = input_junction.codec

        # --- type resolver over the input frame ---
        attr_types = {a.name: a.type for a in definition.attributes
                      if a.type != AttributeType.OBJECT}
        frames = {self.frame_ref: attr_types}
        if self.frame_ref != definition.id:
            frames[definition.id] = attr_types
        codecs = {self.frame_ref: self.codec, definition.id: self.codec}
        # `in Table` conditions reference table attributes (T.attr): add the
        # dep tables' frames so their inner conditions resolve
        for tid in self.dep_tables:
            frames[tid] = dict(self.tables[tid].attr_types)
            codecs[tid] = self.tables[tid].codec
        # unionSet-projection provenance (Attribute.set_projection markers on
        # upstream auto-defined outputs; table markers set at wiring time):
        # the only columns sizeOfSet() accepts downstream
        sp = {a.name for a in definition.attributes
              if getattr(a, "set_projection", False)}
        set_projections = {}
        if sp:
            set_projections[self.frame_ref] = sp
            set_projections[definition.id] = sp
        for tid in self.dep_tables:
            tsp = getattr(self.tables[tid], "set_projection_attrs", None)
            if tsp:
                set_projections[tid] = set(tsp)
        self.resolver = TypeResolver(frames, self.frame_ref, codecs,
                                     set_projections)

        # --- filters ---
        self.filters = [compile_expression(f, self.resolver, registry)
                        for f in in_stream.handlers.filters]
        for f in self.filters:
            if f.type != AttributeType.BOOL:
                raise SiddhiAppCreationError("filter must be boolean")

        self._in_fallbacks, in_nofallback = _collect_in_fallbacks(
            query, self.tables, self.resolver, registry)
        for tid in self._in_fallbacks:
            self.tables[tid]._probe_fallback_ready = True
        for tid in in_nofallback:
            self.tables[tid]._probe_nofallback = True

        # --- stream functions (reference: StreamFunctionProcessor SPI) ---
        # each appends computed columns to the frame; later handlers and the
        # selector see the extended schema. attr_types is the same dict the
        # resolver reads, so extending it here extends name resolution too.
        def _compile_stream_fns(handlers):
            from ..ops.stream_functions import StreamFunctionFactory
            out = []
            for h in handlers:
                factory = registry.require(
                    ExtensionKind.STREAM_FUNCTION, h.namespace, h.name)
                assert isinstance(factory, StreamFunctionFactory)
                arg_ex = [compile_expression(p, self.resolver, registry)
                          for p in h.parameters]
                spec = factory.make(tuple(a.type for a in arg_ex))
                for n, t in spec.new_attrs:
                    attr_types[n] = t
                out.append((spec, arg_ex))
            return out

        self.pre_window_fns = _compile_stream_fns(
            in_stream.handlers.pre_window_functions)
        self.post_window_fns = _compile_stream_fns(
            in_stream.handlers.post_window_functions)
        self.post_filters = [compile_expression(f, self.resolver, registry)
                             for f in in_stream.handlers.post_window_filters]

        # --- window (layout includes stream-function columns) ---
        batch_cap = input_junction.batch_size
        from ..ops.windows import make_layout
        layout = make_layout({a.name: a.type for a in definition.attributes
                              if a.type != AttributeType.OBJECT})
        for spec, _ in self.pre_window_fns:
            for n, t in spec.new_attrs:
                layout[n] = dtypes.device_dtype(t)
                layout.attr_types[n] = t
        # expired-lane emission (reference: outputExpectsExpiredEvents wiring,
        # QueryParser): batch windows only materialize EXPIRED lanes when the
        # query output wants them (`insert all/expired events`) — a CURRENT
        # insert halves the emission chunk the selector sorts. Sliding windows
        # ignore this flag: their expired lanes drive aggregator removal.
        expired_on = query.output_stream.event_type != OutputEventType.CURRENT
        # full-window snapshot (non-aggregated, ungrouped `output snapshot`):
        # the limiter pops its FIFO ring on EXPIRED lanes, so batch windows
        # must materialize them even for CURRENT-only output. The SAME flag
        # later selects the limiter, so the two decisions cannot diverge.
        from ..query_api.execution import OutputRateType
        self._selects_aggs = _selects_aggregates(query.selector, registry)
        # grouped non-aggregated queries snapshot full window contents too
        # (reference GroupByPerSnapshotOutputRateLimiter emits per-group
        # event lists — concatenated, that is every window row)
        self._snapshot_full_window = (
            query.output_rate is not None
            and query.output_rate.type == OutputRateType.SNAPSHOT
            and not self._selects_aggs)
        if self._snapshot_full_window:
            expired_on = True
        wh = in_stream.handlers.window
        self.window: WindowOp = make_window(
            wh, layout, batch_cap, expired_on, registry,
            annotations=query.annotations, playback=bool(ctx.playback))
        if wh is not None:
            et = getattr(ctx, "event_time", None)
            if (et is not None and et.lateness_ms
                    and getattr(self.window, "ts_attr", None) is not None):
                # @app:eventTime + externalTime(Batch): watermark-driven
                # emission — the device watermark trails max-seen by the
                # allowed lateness so panes stay open for rows the ingress
                # gate still buffers. Set BEFORE first trace (static attr).
                self.window.lateness_ms = int(et.lateness_ms)
        # ExpressionWindow shares SlidingState + FIFO suffix semantics, so
        # the removal-capable extrema path (and the grouped-min rejection)
        # applies to it identically
        self.is_sliding_window = wh is not None and type(self.window).__name__ in (
            "SlidingWindow", "ExpressionWindow", "GeneralExpressionWindow")

        # --- selector ---
        select_all = [(a.name, a.type) for a in definition.attributes
                      if a.type != AttributeType.OBJECT]
        for spec, _ in (*self.pre_window_fns, *self.post_window_fns):
            for n, t in spec.new_attrs:
                if n not in dict(select_all):
                    select_all.append((n, t))
        self.selector = CompiledSelector(
            query.selector, self.resolver, registry,
            ctx.effective_group_capacity, self.frame_ref,
            select_all_attrs=select_all,
            sliding_window=self.is_sliding_window)
        if self.selector.extrema_plan:
            # the range-query extrema path reads WINDOW contents; shapes
            # where window membership diverges from what the aggregator may
            # see are rejected rather than silently diverging
            if self.post_filters:
                raise SiddhiAppCreationError(
                    "min()/max() over a sliding window cannot combine with a "
                    "post-window filter (filtered rows remain in the window); "
                    "filter before the window instead")
            if getattr(self.window, "is_delay", False):
                raise SiddhiAppCreationError(
                    "min()/max() over #window.delay is not supported "
                    "(delay re-emits expired lanes as arrivals)")

        # --- output stream definition ---
        # forwarded raw-unionSet slots carry the set-size projection with a
        # provenance marker so ONLY they satisfy downstream sizeOfSet()
        self.output_attributes = tuple(
            Attribute(name, t,
                      set_projection=name in self.selector.host_set_slots)
            for name, t in self.selector.out_types.items())
        self.output_definition = StreamDefinition(
            id=query.output_stream.target_id or f"{self.name}_out",
            attributes=self.output_attributes)
        self.output_codec = self._build_output_codec()

        # --- output rate limiter ---
        from ..ops.ratelimit import make_rate_limiter
        out_layout = {n: dtypes.device_dtype(t)
                      for n, t in self.selector.out_types.items()
                      if t != AttributeType.OBJECT}  # host-only slots
        from ..ops.windows import (LengthBatchWindow, SlidingWindow,
                                   TimeBatchWindow, WindowOp as _WindowOp)
        fifo = isinstance(self.window,
                          (SlidingWindow, LengthBatchWindow, TimeBatchWindow))
        # non-FIFO windows with a findable surface (sort/session/frequent/
        # cron/hopping): snapshots read the ring's live set directly
        findable = type(self.window).contents is not _WindowOp.contents \
            and not isinstance(self.window, PassThroughWindow)
        self.rate_limiter = make_rate_limiter(
            query.output_rate, out_layout, self.window.chunk_width,
            grouped=bool(query.selector.group_by),
            group_capacity=ctx.effective_group_capacity,
            fifo_window=fifo and self._snapshot_full_window,
            has_aggregates=self._selects_aggs,
            window_capacity=getattr(self.window, "C", 0),
            contents_window=findable and self._snapshot_full_window)
        from ..ops.ratelimit import (ContentsSnapshotLimiter,
                                     GroupedSnapshotLimiter)
        if isinstance(self.rate_limiter, GroupedSnapshotLimiter):
            # the limiter retains one row per group: have the selector ride
            # each lane's group slot on a pseudo-column (set before tracing)
            self.selector.expose_group_slot = True
        if isinstance(self.rate_limiter, ContentsSnapshotLimiter):
            if self.post_window_fns or self.post_filters:
                raise SiddhiAppCreationError(
                    "`output snapshot` over a non-FIFO window cannot combine "
                    "with post-window functions/filters (snapshots re-project "
                    "the raw window contents); apply them before the window")
            if query.selector.order_by or query.selector.limit is not None \
                    or query.selector.offset is not None:
                raise SiddhiAppCreationError(
                    "`output snapshot` over a non-FIFO window cannot combine "
                    "with order by / limit / offset (snapshots re-emit the "
                    "whole live window set)")

        # --- shape-bucketed dispatch eligibility ---
        # the junction pads partial batches to power-of-two lane buckets;
        # a query whose whole step derives lane counts from the batch
        # (shape-polymorphic window, no ring-vs-chunk extrema coupling)
        # consumes them directly, compiling once per ladder rung. Everything
        # else pads back to the planned capacity in on_batch (one compile).
        self._batch_cap = input_junction.batch_size
        self._bucket_ok = (self.window.shape_polymorphic
                          and not self.selector.extrema_plan)

        # --- the jitted step ---
        self._step = jax.jit(self._make_step(), donate_argnums=(0,))
        self.state = self._init_state()
        #: set by core/shared.py when this query's step body is traced into
        #: a SharedStepGroup's fused jit: the junction then delivers to the
        #: group (this runtime's own _step stays cold), but state/callbacks/
        #: output wiring remain per-query, so persistence and upgrade see
        #: exactly the unfused layout
        self._fused_group = None
        self._has_custom_aggs = any(
            spec.custom_scan is not None for _, spec, _ in self.selector.agg_specs)
        self._batches_seen = 0
        #: a sliding window's account (statistics_report()["windows"]): its
        #: counters live in its state on the device and are synced at a
        #: report, the two loss counters also at every 64th step
        from ..ops.windows import SlidingWindow as _Sliding
        self.cells = (StageCells(("drop_sync",))
                      if isinstance(self.window, _Sliding) else None)
        self._out_lanes = 0
        #: the lanes the selector ran over, summed on the device (the step
        #: takes and returns it): a statistic, in no snapshot
        self._selector_lanes = (jnp.int64(0) if self.cells is not None
                                else None)
        self._loss_warned = False
        self.synced = {"live": 0, "live_hwm": 0, "appended": 0, "expired": 0,
                       "ring_overflow": 0, "expiry_deferred": 0,
                       "selector_lanes": 0}
        self._capacity_warned = False
        self._capacity_pressure = False
        self._snapshot_warned = False
        self._last_compacted_live: dict[int, int] = {}
        #: time-driven windows need heartbeats to flush expirations
        from ..ops.windows import window_has_time_semantics
        self.has_time_semantics = (
            window_has_time_semantics(self.window)
            or self.rate_limiter.has_time_semantics)

    # ----------------------------------------------------------------- plan

    def _build_output_codec(self) -> StreamCodec:
        """String codes are app-global (ctx.global_strings), so output string
        columns decode directly regardless of which source attr produced them."""
        return StreamCodec(self.output_definition, self.ctx.global_strings)

    def _init_state(self):
        return (self.window.init_state(), self.selector.init_state(),
                self.rate_limiter.init_state())

    def _make_step(self, track_compiles: bool = True):
        import dataclasses as dc

        filters = self.filters
        post_filters = self.post_filters
        pre_fns = self.pre_window_fns
        post_fns = self.post_window_fns
        window = self.window
        selector = self.selector
        frame_ref = self.frame_ref
        dep_tables = self.dep_tables
        probes = {tid: self.tables[tid].contains_probe for tid in dep_tables}
        for tid in dep_tables:
            if hasattr(self.tables[tid], "_used_in_probe"):
                self.tables[tid]._used_in_probe = True  # cache-miss monitor

        limiter = self.rate_limiter
        stats = self.ctx.statistics
        qname = self.name

        def apply_fns(fns, batch, scope):
            for spec, arg_ex in fns:
                args = [a(scope) for a in arg_ex]
                new_cols = spec.apply(*args)
                declared = dict(spec.new_attrs)
                cast_cols = {
                    n: jnp.asarray(c).astype(dtypes.device_dtype(declared[n]))
                    for n, c in new_cols.items()}
                batch = dc.replace(batch, cols={**batch.cols, **cast_cols})
                scope.add_frame(frame_ref, batch.cols, batch.ts, batch.valid,
                                default=True)
            return batch

        def step(state, batch: EventBatch, now, table_states=None,
                 selector_lanes=None):
            # trace-time side effect: fires once per compiled executable —
            # the per-query compile counter (recompile-storm observability).
            # Fused members suppress it: the SharedStepGroup counts ONE
            # compile for the whole group under its own name.
            if track_compiles:
                stats.track_compile(qname, batch.capacity)
            wstate, sstate, rstate = state

            scope = Scope()
            scope.add_frame(frame_ref, batch.cols, batch.ts, batch.valid, default=True)
            scope.extras["now"] = now
            if table_states:
                for tid, (tstate, tidx) in table_states.items():
                    scope.extras[f"table:{tid}"] = tstate
                    scope.extras[f"tableidx:{tid}"] = tidx
                    scope.extras[f"in:{tid}"] = probes[tid]
            with stage("filter"):
                mask = batch.valid
                for f in filters:
                    mask = mask & f(scope)
                batch = batch.where_valid(mask)
                scope.add_frame(frame_ref, batch.cols, batch.ts, batch.valid,
                                default=True)
                batch = apply_fns(pre_fns, batch, scope)

            wstate_pre = wstate
            with stage("window"):
                wstate, chunk = window.step(wstate, batch, now)

            with stage("selector"):
                cscope = Scope()
                cscope.add_frame(frame_ref, chunk.cols, chunk.ts, chunk.valid, default=True)
                cscope.extras = dict(scope.extras)
                chunk = apply_fns(post_fns, chunk, cscope)
                for f in post_filters:
                    chunk = chunk.where_valid(
                        f(cscope) | (chunk.types != EventType.CURRENT))
                if selector.extrema_plan:
                    # removal-capable sliding min/max: range queries over the
                    # window's arrival-order sequence (ops/extrema.py)
                    from ..ops.extrema import (grouped_sliding_extrema_lanes,
                                               sliding_extrema_lanes)
                    from ..ops.windows import _unpack_rows
                    ring_cols, ring_ts = _unpack_rows(wstate_pre.ring,
                                                      window.layout)
                    rscope = Scope()
                    rscope.add_frame(
                        frame_ref, ring_cols, ring_ts,
                        jnp.ones(ring_ts.shape, bool), default=True)
                    rscope.extras = dict(scope.extras)
                    ghash = selector.extrema_group_hash
                    for slot, eop, args in selector.extrema_plan:
                        if ghash is not None:
                            cscope.extras[f"extrema:{slot}"] = \
                                grouped_sliding_extrema_lanes(
                                    eop, args[0](rscope), ghash(rscope),
                                    wstate_pre.expired, wstate_pre.appended,
                                    chunk, args[0](cscope), ghash(cscope))
                        else:
                            cscope.extras[f"extrema:{slot}"] = \
                                sliding_extrema_lanes(
                                    eop, args[0](rscope), wstate_pre.expired,
                                    wstate_pre.appended, chunk, args[0](cscope))
                width = selector_round_width(window, selector,
                                             cscope.extras, chunk.capacity)
                if width is None:
                    sstate, out = selector.step(sstate, chunk, cscope)
                    lanes = chunk.capacity
                else:
                    sstate, out, lanes = selector.step_in_rounds(
                        sstate, chunk, cscope, width)
            with stage("emit"):
                if getattr(limiter, "needs_window_contents", False):
                    # non-FIFO snapshot: per-arrival output is suppressed; ticks
                    # re-project the window's live contents. POST-step state so
                    # time-driven evictions (session close on this watermark)
                    # apply; the limiter then drops rows whose arrival ts is
                    # PAST the fired boundary, so the batch revealing a crossing
                    # cannot leak its later arrivals into that snapshot
                    w_cols, w_ts, w_live = window.contents(wstate, now)
                    s2 = Scope()
                    s2.add_frame(frame_ref, w_cols, w_ts, w_live, default=True)
                    s2.extras["now"] = now
                    proj = {
                        name: jnp.broadcast_to(
                            jnp.asarray(ce(s2)), w_ts.shape)
                        for name, ce in selector.out_exprs}
                    if selector.having is not None:
                        h2 = Scope()
                        h2.add_frame(frame_ref, w_cols, w_ts, w_live)
                        h2.add_frame("__out__", proj, w_ts, w_live, default=True)
                        h2.extras["now"] = now
                        w_live = w_live & selector.having(h2)
                    cb = EventBatch(  # ts = ARRIVAL instants (boundary filter)
                        ts=w_ts, cols=proj, valid=w_live,
                        types=jnp.zeros(w_ts.shape, jnp.int8))
                    rstate, out = limiter.step_contents(rstate, cb, now)
                else:
                    rstate, out = limiter.step(rstate, out, now)

            if selector_lanes is None:
                return (wstate, sstate, rstate), out
            # the lanes the selector ran over, summed: a statistic, carried
            # beside the state and never in a snapshot
            return (wstate, sstate, rstate), out, selector_lanes + lanes

        return step

    def take_step(self, step, state) -> None:
        """Run `step(state, batch, now, table_states) -> (state, out)` on
        `state` from here on, in place of the planned window -> selector ->
        limiter step: a partition's keyed step (core/keyed_partition.py),
        whose state has a key axis. The step reads its width from the
        batch, and its window keeps no `SlidingState`, so this query leaves
        the `windows` section of the statistics."""
        self._step = jax.jit(step, donate_argnums=(0,))
        self.state = state
        self._bucket_ok = True
        self.cells = None
        self._selector_lanes = None

    # -------------------------------------------------------------- runtime

    def _selector_state(self):
        """The selector's slice of this runtime's state tuple (joins keep it
        at a different index — see JoinQueryRuntime)."""
        return self.state[1]

    def _maybe_in_fallback(self, batch: EventBatch, now: int) -> None:
        """Pre-warm overflowed `in`-probed caches with this batch's probe
        values (host store read-through before the jitted step) — see
        RecordTableRuntime.ensure_cached_for_keys."""
        scope = None
        for tid, specs in self._in_fallbacks.items():
            table = self.tables[tid]
            pol = getattr(table, "cache_policy", None)
            if pol is None or not pol.overflowed:
                continue
            if scope is None:
                scope = Scope()
                scope.add_frame(self.frame_ref, batch.cols, batch.ts,
                                batch.valid, default=True)
                scope.extras["now"] = jnp.int64(now)
            for t_attr, sc, stype in specs:
                try:
                    vals_dev = sc(scope)
                except Exception:  # expr needs step-computed columns: skip
                    continue
                import numpy as np
                valid, vals = jax.device_get((batch.valid, vals_dev))
                sel = np.asarray(vals)[np.nonzero(valid)[0]]
                if stype == AttributeType.STRING:
                    keys = table.codec.string_tables[t_attr].decode_array(
                        sel.tolist())
                elif stype == AttributeType.BOOL:
                    keys = sel.astype(bool).tolist()
                else:
                    keys = sel.tolist()
                table.ensure_cached_for_keys((t_attr,),
                                             {(k,) for k in keys})

    def _table_states(self) -> dict:
        return {tid: (self.tables[tid].state,
                      self.tables[tid].probe_indexes()
                      if tid in self._index_tables else {})
                for tid in self.dep_tables}

    def warmup(self, buckets=None) -> int:
        """AOT-compile the jitted step for each lane bucket (ahead of time,
        WITHOUT executing — see aot_warm), so first-batch compile time never
        pollutes steady-state latency/throughput. Returns the number of
        fresh compiles this triggered."""
        if buckets is None:
            buckets = (dtypes.bucket_ladder(self._batch_cap)
                       if self._bucket_ok and dtypes.config.shape_buckets
                       and self.ctx.mesh is None else (self._batch_cap,))
        n0 = self.ctx.statistics.compiles.get(self.name, 0)
        now = jnp.int64(self.ctx.timestamp_generator.current_time())
        for cap in buckets:
            batch = EventBatch.empty(self.input_junction.definition, cap)
            lanes = (() if self._selector_lanes is None
                     else (self._selector_lanes,))
            aot_warm(self._step, self.state, batch, now,
                     self._table_states(), *lanes)
            warm = getattr(self.table_executor, "warm", None)
            if warm is not None:
                warm(EventBatch.empty(self.output_definition, cap),
                     self.query.output_stream.event_type)
        return self.ctx.statistics.compiles.get(self.name, 0) - n0

    def on_batch(self, batch: EventBatch, now: int) -> None:
        t0 = time.perf_counter_ns()
        if batch.capacity < self._batch_cap and not self._bucket_ok:
            # shape-baked step: restore the traced capacity (bucketed or
            # upstream-chunked batches widen; new lanes are invalid)
            batch = batch.pad_to(self._batch_cap)
        debugger = getattr(self.ctx, "debugger", None)
        if debugger is not None:
            from .debugger import QueryTerminal
            if debugger.wants(self.name, QueryTerminal.IN):
                debugger.check_break_point(
                    self.name, QueryTerminal.IN,
                    batch.to_host_events(self.codec))
        if self._in_fallbacks:
            self._maybe_in_fallback(batch, now)
        traced = self.ctx.statistics.compiles.get(self.name, 0)
        if self._selector_lanes is None:
            self.state, out = self._step(self.state, batch, jnp.int64(now),
                                         self._table_states())
        else:
            self.state, out, self._selector_lanes = self._step(
                self.state, batch, jnp.int64(now), self._table_states(),
                self._selector_lanes)
        traced = self.ctx.statistics.compiles.get(self.name, 0) != traced
        self._out_lanes += out.capacity
        self._distribute(out, now)
        elapsed = time.perf_counter_ns() - t0
        self.ctx.statistics.track_latency(self.name, elapsed)
        meter = getattr(self.ctx, "tenant_meter", None)
        if meter is not None:
            meter.record(self.name, elapsed)
        tele = getattr(self.ctx, "telemetry", None)
        if tele is not None and tele.on:
            tele.record_query(self.name, elapsed, compiled=traced)
        self._post_step_maintenance()

    def _post_step_maintenance(self) -> None:
        """Per-batch housekeeping after the jitted step: custom-aggregate
        compaction cadence + snapshot-overflow warning. Shared between
        on_batch and SharedStepGroup dispatch (core/shared.py)."""
        self._batches_seen += 1
        if (self.cells is not None and not self._loss_warned
                and self._batches_seen % 64 == 0):
            self._sync_window_loss()
        # adaptive cadence: cheap (one scalar sync) but sparse normally;
        # tight once a table runs hot so compaction outruns overflow.
        # Warnings are one-shot, but the checks (and their compactions)
        # keep running for the app's lifetime.
        interval = 4 if self._capacity_pressure else 256
        if (self._has_custom_aggs
                and (self._batches_seen in (1, 16, 64)
                     or self._batches_seen % interval == 0)):
            self._check_custom_agg_capacity()
        if (not self._snapshot_warned and self._batches_seen % 256 == 0
                and hasattr(self.state[2], "overflow")):
            if int(self.state[2].overflow) > 0:
                import warnings
                warnings.warn(
                    f"query {self.name!r}: {int(self.state[2].overflow)} "
                    "output lanes exceeded snapshot_group_capacity and are "
                    "missing from periodic snapshots — raise "
                    "config.snapshot_group_capacity", stacklevel=2)
                self._snapshot_warned = True

    def _sync_window_loss(self) -> None:
        """The window's two loss counters, as the join's and the pattern's
        drop counters: an `int()` under the controller lock waits for every
        step dispatched so far, so every 64th step and not each."""
        wstate = self.state[0]
        with self.cells.span("drop_sync", "siddhi.window.drop_sync"):
            lost = jax.device_get((wstate.overflow, wstate.deferred,
                                   self._selector_lanes))
        (self.synced["ring_overflow"], self.synced["expiry_deferred"],
         self.synced["selector_lanes"]) = (int(v) for v in lost)
        if sum(self.synced[k] for k in ("ring_overflow", "expiry_deferred")):
            import warnings
            warnings.warn(
                f"query {self.name!r}: the window overwrote "
                f"{self.synced['ring_overflow']} live rows and let "
                f"{self.synced['expiry_deferred']} rows go late (more due "
                "in one step than its expiry width) — raise "
                "@capacity(window=..., expire=...) on the query",
                stacklevel=2)
            self._loss_warned = True

    def device_counters(self, report: bool = False) -> dict:
        """Copies of the window state's counters for collect_overflow()'s
        one fetch (under the controller lock: the next step donates the
        state); `sync_counters` takes the values back. `report`: the caller
        is statistics_report(), at which the high water starts anew."""
        ws = self.state[0]
        live = ws.appended - ws.expired
        held = {"live": live, "live_hwm": ws.live_hwm,
                "appended": ws.appended, "expired": ws.expired,
                "ring_overflow": ws.overflow, "expiry_deferred": ws.deferred,
                "selector_lanes": self._selector_lanes}
        held = {k: jnp.copy(v) for k, v in held.items()}
        if report:
            self.state = (ws._replace(live_hwm=live), *self.state[1:])
        return held

    def sync_counters(self, fetched: dict) -> None:
        self.synced.update({k: int(v) for k, v in fetched.items()})

    def stats_snapshot(self) -> dict:
        """statistics_report()["windows"][name], for a query over a sliding
        window. `steps`, `out_lanes` (the out block's lanes, valid or not:
        what the read-back fetches), `selector_lanes` (the lanes the
        selector ran over: the chunk's width a step, or its rounds times
        the batch where it runs in rounds), `appended` and `expired` are
        cumulative; `live` is the rows in the window after the last step
        synced, `live_hwm` the most since the statistics_report() before;
        the two loss counters are the device's as last synced."""
        return {
            "capacity": self.window.C,
            "expire_width": self.window.E,
            "steps": self._batches_seen,
            "out_lanes": self._out_lanes,
            **self.synced,
            "stage_ms": self.cells.snapshot(),
        }

    def _check_custom_agg_capacity(self) -> None:
        """distinctCount's (group,value) pair table is append-only inside
        the jitted step (zeroed pairs keep their slot, unlike the reference's
        HashMap entry removal). At 85% occupancy the monitor COMPACTS it —
        rebuilding with only live pairs (ops/aggregators.py
        compact_distinct_state) — and only warns if live pairs alone still
        exceed capacity."""
        import dataclasses as dc
        import warnings

        from ..ops.aggregators import compact_distinct_state
        from ..ops.groupby import GroupState, KeyTable
        pressure = False
        for gi, g in enumerate(self.state[1].groups):
            if not (isinstance(g, tuple) and g):
                continue
            if isinstance(g[0], KeyTable):
                kt = g[0]
                cap = kt.keys.shape[0] // 2  # hash array is 2x id capacity
                count = int(kt.count)
                pressure = pressure or count > int(0.5 * cap)
                # compact early enough that the table cannot fill (and
                # start dropping pairs) between checks — but only when
                # enough NEW pairs arrived since the last rebuild that dead
                # ones can plausibly be reclaimed (a steady 0.6*cap LIVE
                # set must not trigger an O(cap) rebuild every check)
                grown = count - self._last_compacted_live.get(gi, 0)
                if (count > int(0.85 * cap)
                        or (count > int(0.5 * cap)
                            and grown > int(0.2 * cap))):
                    sstate = self.state[1]
                    epoch = int(sstate.epoch)
                    new_g = compact_distinct_state(g, epoch)
                    groups = list(sstate.groups)
                    groups[gi] = new_g
                    self.state = (self.state[0],
                                  dc.replace(sstate, groups=groups),
                                  self.state[2])
                    kt = new_g[0]
                    self._last_compacted_live[gi] = int(kt.count)
                    if (int(kt.count) > int(0.85 * cap)
                            and not self._capacity_warned):
                        warnings.warn(
                            f"query {self.name!r}: distinctCount pair table "
                            f"still at {int(kt.count)}/{cap} LIVE "
                            "(group,value) pairs after compaction; counts "
                            "will corrupt past capacity — raise "
                            "group_capacity", stacklevel=2)
                        self._capacity_warned = True
                elif int(kt.misses) > 0 and not self._capacity_warned:
                    warnings.warn(
                        f"query {self.name!r}: {int(kt.misses)} key lookups "
                        "could not be placed and their events were dropped "
                        "from the aggregate — raise group_capacity",
                        stacklevel=2)
                    self._capacity_warned = True
            elif isinstance(g[0], GroupState) and len(g) == 2:
                # string-code fast path: pair table indexed by interning code
                cap = g[0].values.shape[0]
                n_codes = len(self.ctx.global_strings)
                if n_codes > int(0.85 * cap) and not self._capacity_warned:
                    warnings.warn(
                        f"query {self.name!r}: distinctCount code table at "
                        f"{n_codes}/{cap} interned strings; codes past "
                        "capacity are dropped from the count — raise "
                        "group_capacity", stacklevel=2)
                    self._capacity_warned = True
        self._capacity_pressure = pressure

    def _distribute(self, out: EventBatch, now: int) -> None:
        action = self.query.output_stream.action
        etype = self.query.output_stream.event_type

        debugger = getattr(self.ctx, "debugger", None)
        if (debugger is None and not self.callbacks
                and action == OutputAction.INSERT
                and self.output_junction is not None
                and _sink_dark(self.output_junction)):
            # nothing observes this emission: skip the _select_event_type
            # device ops and the controller-lock publish round trip. For
            # fan-out apps (N queries, few subscribed outputs) this is the
            # dominant per-query per-batch cost.
            return
        if debugger is not None:
            from .debugger import QueryTerminal
            if debugger.wants(self.name, QueryTerminal.OUT):
                debugger.check_break_point(
                    self.name, QueryTerminal.OUT,
                    out.to_host_events(self.output_codec))

        uuid_slots = self.selector.host_uuid_slots
        forwards = (self.output_junction is not None
                    or self.table_executor is not None)
        if uuid_slots and forwards:
            # fresh uuid4 per emitted lane per UUID() slot (reference
            # UUIDFunctionExecutor), interned into the string table's
            # BOUNDED transient ring so every consumer — downstream
            # queries, tables, sinks — sees real values with O(1) host
            # memory (codes recycle after ~1M newer uuids; docs/PARITY.md)
            out = self._intern_uuid_columns(out)

        if self.callbacks:
            # callbacks see exactly what the query emits (reference:
            # outputExpectsExpiredEvents): CURRENT-only queries get no
            # removeEvents regardless of window kind
            events = out.to_host_events(self.output_codec)
            set_slots = getattr(self.selector, "host_set_slots", None)
            if set_slots and events:
                # raw unionSet: materialize the live value set host-side
                # (reference UnionSetAttributeAggregatorExecutor.java:71 —
                # every emission carries the SAME accumulating set object;
                # here each batch's events share one materialized set)
                names = [a.name for a in self.output_attributes]
                subs = [(names.index(n),
                         self.selector.union_set_values(
                             self._selector_state(), n,
                             self.ctx.global_strings))
                        for n in set_slots]
                for k, e in enumerate(events):
                    data = list(e.data)
                    for i, s in subs:
                        data[i] = s
                    events[k] = Event(e.timestamp, tuple(data),
                                      is_expired=e.is_expired)
            if uuid_slots and not forwards and events:
                # callback-only output: substitute decoded events directly —
                # no interning, no string-table growth
                import uuid as _uuid
                names = [a.name for a in self.output_attributes]
                idxs = [names.index(s) for s in uuid_slots]
                for k, e in enumerate(events):
                    data = list(e.data)
                    for i in idxs:
                        data[i] = str(_uuid.uuid4())
                    # Event is frozen (GC-untrack safety): rebuild
                    events[k] = Event(e.timestamp, tuple(data),
                                      is_expired=e.is_expired)
            in_events = [e for e in events if not e.is_expired] or None
            remove_events = ([e for e in events if e.is_expired] or None
                             if etype != OutputEventType.CURRENT else None)
            if etype == OutputEventType.EXPIRED:
                in_events = None
            if in_events or remove_events:
                for cb in self.callbacks:
                    cb.receive(now, in_events, remove_events)

        if action == OutputAction.INSERT and self.output_junction is not None:
            fwd = self._select_event_type(out, etype)
            self.output_junction.publish_batch(fwd, now)
        elif action in (OutputAction.DELETE, OutputAction.UPDATE,
                        OutputAction.UPDATE_OR_INSERT) and self.table_executor is not None:
            if getattr(self.table_executor, "takes_output", False):
                # one table-write program selects and writes
                self.table_executor.apply_output(out, etype)
                return
            fwd = self._select_event_type(out, etype)
            self.table_executor.apply(fwd)

    def _intern_uuid_columns(self, out: EventBatch) -> EventBatch:
        import dataclasses as dc
        import uuid as _uuid

        import numpy as np
        valid = np.asarray(out.valid)
        idx = np.nonzero(valid)[0]
        cols = dict(out.cols)
        for slot in self.selector.host_uuid_slots:
            tbl = self.output_codec.string_tables[slot]
            codes = np.zeros(out.capacity, np.int32)
            for i in idx:
                codes[i] = tbl.encode_transient(str(_uuid.uuid4()))
            cols[slot] = jnp.asarray(codes)
        return dc.replace(out, cols=cols)

    @staticmethod
    def _select_event_type(out: EventBatch, etype: OutputEventType) -> EventBatch:
        import dataclasses as dc
        if etype == OutputEventType.CURRENT:
            keep = out.types == EventType.CURRENT
        elif etype == OutputEventType.EXPIRED:
            keep = out.types == EventType.EXPIRED
        else:
            keep = (out.types == EventType.CURRENT) | (out.types == EventType.EXPIRED)
        # forwarded events enter the next stream as fresh CURRENT arrivals
        return dc.replace(out, valid=out.valid & keep,
                          types=jnp.zeros_like(out.types))

    def add_callback(self, cb: QueryCallback) -> None:
        self.callbacks.append(cb)


def _sink_dark(j) -> bool:
    """True when publishing to junction `j` is observably a no-op: no
    receivers, taps, WAL, blue-green redirect, or staged rows, and
    statistics (explicit opt-in, exact in/out counts) are off. Re-checked
    per batch, so attaching a callback or subscriber later re-lights the
    sink immediately. Always-on telemetry does NOT keep a sink lit: its
    spans measure delivery work, and a skipped no-op delivery has none —
    dark streams simply stop appearing in per-stream batch series
    (docs/OPTIMIZER.md)."""
    if not isinstance(j, StreamJunction):
        # window/table junction adapters always consume their input
        return False
    if j.receivers or j.taps or j._staged_rows:
        return False
    if j.wal is not None or j._redirect is not None:
        return False
    return not j.ctx.statistics.enabled


def _collect_eq_probe_tables(query: Query, tables: dict) -> set:
    """Tables probed by a single-equality `in` condition on an indexable
    attribute — the only ones whose sorted indexes the step will read."""
    from ..query_api.expression import Compare, CompareOp, In

    found: set = set()

    def walk(node):
        if node is None or not isinstance(node, Expression):
            return
        if isinstance(node, In):
            e = node.expression
            t = tables.get(node.source_id)
            if (t is not None and isinstance(e, Compare)
                    and e.op == CompareOp.EQUAL
                    and hasattr(t, "indexable_eq_attrs")):
                for side in (e.left, e.right):
                    if (isinstance(side, Variable)
                            and side.stream_id == node.source_id
                            and side.attribute in t.indexable_eq_attrs()):
                        found.add(node.source_id)
            walk(e)
            return
        for attr in ("left", "right", "expression"):
            sub = getattr(node, attr, None)
            if isinstance(sub, Expression):
                walk(sub)
        for p in getattr(node, "parameters", ()) or ():
            walk(p)

    for f in query.input_stream.handlers.filters:
        walk(f)
    for f in query.input_stream.handlers.post_window_filters:
        walk(f)
    walk(query.selector.having)
    return found


def _collect_in_fallbacks(query: Query, tables: dict, resolver, registry):
    """Per cached-@store table id: [(table_attr, compiled_stream_expr, type)]
    for every `T.attr == <stream expr>` `in` condition — the store-fallback
    key plans (reference: AbstractQueryableRecordTable.java:207-238).
    Returns (fallbacks, nofallback_table_ids): the second set lists cached
    tables probed by at least one `in` condition NO fallback covers (their
    overflow warning must stay the hard miss warning)."""
    from ..io.record_table import RecordTableRuntime
    from ..query_api.expression import Compare, CompareOp, In

    found: dict = {}
    nofallback: set = set()

    def consider(node: In):
        t = tables.get(node.source_id)
        if not (isinstance(t, RecordTableRuntime) and t.cache_policy is not None):
            return
        e = node.expression
        if isinstance(e, Compare) and e.op == CompareOp.EQUAL:
            for tside, sside in ((e.left, e.right), (e.right, e.left)):
                if not (isinstance(tside, Variable)
                        and tside.stream_id == node.source_id):
                    continue
                if _references_table_frame(sside, node.source_id):
                    continue
                try:
                    sc = compile_expression(sside, resolver, registry)
                except SiddhiAppCreationError:
                    continue
                found.setdefault(node.source_id, []).append(
                    (tside.attribute, sc, sc.type))
                return
        nofallback.add(node.source_id)

    def walk(node):
        if node is None or not isinstance(node, Expression):
            return
        if isinstance(node, In):
            consider(node)
            walk(node.expression)
            return
        for attr in ("left", "right", "expression"):
            sub = getattr(node, attr, None)
            if isinstance(sub, Expression):
                walk(sub)
        for p in getattr(node, "parameters", ()) or ():
            walk(p)

    for f in query.input_stream.handlers.filters:
        walk(f)
    for f in query.input_stream.handlers.post_window_filters:
        walk(f)
    for a in query.selector.attributes:
        walk(a.expression)
    walk(query.selector.having)
    return found, nofallback


def _references_table_frame(e, frame: str) -> bool:
    if isinstance(e, Variable):
        return e.stream_id == frame
    for attr in ("left", "right", "expression"):
        sub = getattr(e, attr, None)
        if isinstance(sub, Expression) and _references_table_frame(sub, frame):
            return True
    return any(_references_table_frame(p, frame)
               for p in getattr(e, "parameters", ()) or ()
               if isinstance(p, Expression))


def _collect_in_sources(query: Query) -> set[str]:
    """Table ids referenced by `in Table` conditions anywhere in the query."""
    from ..query_api.expression import In

    found: set[str] = set()

    def walk(node):
        if node is None or not isinstance(node, Expression):
            return
        if isinstance(node, In):
            found.add(node.source_id)
            walk(node.expression)
            return
        for attr in ("left", "right", "expression"):
            sub = getattr(node, attr, None)
            if isinstance(sub, Expression):
                walk(sub)
        for p in getattr(node, "parameters", ()) or ():
            walk(p)

    ins = query.input_stream
    for f in getattr(ins.handlers, "filters", ()):
        walk(f)
    for f in getattr(ins.handlers, "post_window_filters", ()):
        walk(f)
    for a in query.selector.attributes:
        walk(a.expression)
    walk(query.selector.having)
    return found
