"""Join query runtime (reference: core/query/input/stream/join/JoinProcessor.java:45,
JoinInputStreamParser.java:75).

One runtime serves `from L#w() join R#w() on cond`. Each side keeps its own
window ring; a batch arriving on a triggering side is appended to its own
window and probed against the *opposite* side's current contents (the
reference's `find()` with a CompiledCondition becomes a batched sort-merge /
cross probe — ops/join.py). Table sides probe the table's device state.

Ordering note (divergence, documented): within one micro-batch of a self-join,
intra-batch pairs are not emitted (each batch probes the opposite ring as of
the previous flush). Across junction flushes the reference's per-event
interleaving is preserved.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..errors import DefinitionNotExistError, SiddhiAppCreationError
from ..extension.registry import Registry
from ..ops.expr_compile import Scope, TypeResolver, compile_expression
from ..ops.join import (JoinPlan, _hash_exprs, collect_vars, compact_pairs,
                        multimap_append, multimap_buckets, multimap_init,
                        plan_join, probe_cross, probe_equi, probe_equi_mm)
from ..ops.selector import CompiledSelector
from ..ops.window_factories import make_window
from ..ops.windows import (SlidingWindow, WindowOp, _pack_rows,
                           _unpack_rows)
from ..query_api.definition import Attribute, AttributeType, StreamDefinition
from ..query_api.execution import (
    EventTrigger,
    JoinInputStream,
    JoinType,
    OutputAction,
    Query,
    SingleInputStream,
)
from ..telemetry.tracing import StageCells, stage
from . import dtypes, table_index
from .context import SiddhiAppContext
from .event import EventBatch, EventType, StreamCodec
from .query_runtime import QueryCallback
from .stream import Receiver, StreamJunction


def _qualify_for_store(expr, probe_side, table_side, resolver):
    """Rewrite a join ON condition for the store walk: table-side variables
    (by RESOLVER classification — aliases and unqualified attrs included)
    get the table DEFINITION id (walk_condition's table_id), probe-side
    variables get the probe ref (the parameter-name prefix). Variables
    resolving to neither frame raise — no fallback for them."""
    import dataclasses as _dc

    from ..ops.join import frames_of
    from ..query_api.expression import Expression, Variable
    table_id = table_side.table.definition.id

    from ..query_api.expression import IsNull

    def walk(e):
        if isinstance(e, IsNull) and isinstance(e.expression, Variable):
            fr = frames_of(e.expression, resolver)
            if not fr <= {table_side.ref}:
                # walk_condition's isNull compiles against the TABLE row
                # only — a probe-side null test would silently evaluate the
                # wrong column; no fallback for those conditions
                raise SiddhiAppCreationError(
                    "store fallback cannot express probe-side isNull")
            return _dc.replace(e, expression=_dc.replace(
                e.expression, stream_id=table_id))
        if isinstance(e, Variable):
            fr = frames_of(e, resolver)
            if fr <= {table_side.ref}:
                return _dc.replace(e, stream_id=table_id)
            if fr <= {probe_side.ref}:
                return _dc.replace(e, stream_id=probe_side.ref)
            raise SiddhiAppCreationError(
                f"store fallback cannot classify {e.attribute!r}")
        kw = {}
        for a in ("left", "right", "expression"):
            sub = getattr(e, a, None)
            if isinstance(sub, Expression):
                kw[a] = walk(sub)
        if getattr(e, "parameters", None):
            return _dc.replace(e, parameters=tuple(
                walk(p) if isinstance(p, Expression) else p
                for p in e.parameters))
        if kw:
            return _dc.replace(e, **kw)
        return e

    return walk(expr)


class _Side:
    """One join side: a stream (junction + window), a table, or a named
    window (probed via its shared contents; its emissions also trigger)."""

    def __init__(self, ins: SingleInputStream, ctx, registry, junctions, tables,
                 windows=None, aggregations=None, per=None, annotations=()):
        self.ref = ins.reference_id  # alias or stream id
        self.stream_id = ins.stream_id
        self.is_table = ins.stream_id in tables
        self.table = tables.get(ins.stream_id)
        if self.is_table:
            from ..io.record_table import RecordTableRuntime
            if isinstance(self.table, RecordTableRuntime):
                if self.table.cache is None:
                    raise SiddhiAppCreationError(
                        f"record table {ins.stream_id!r} has no @cache: joins "
                        "probe tables inside the jitted step and need "
                        "@cache(size='N', policy='FIFO|LRU|LFU')")
                self.table._used_in_probe = True  # cache-miss monitor
        self.named_window = (windows or {}).get(ins.stream_id)
        self.is_named_window = self.named_window is not None and not self.is_table
        self.aggregation = (aggregations or {}).get(ins.stream_id)
        self.is_aggregation = (self.aggregation is not None and not self.is_table
                               and not self.is_named_window)
        self.agg_view = None
        self.junction: Optional[StreamJunction] = None
        self.window: Optional[WindowOp] = None
        self.filters = []
        if self.is_aggregation:
            # `from S join Agg per "duration" on ...` (reference:
            # AggregationRuntime.compileExpression:384+ / JoinInputStreamParser).
            # Divergence, documented: `within start, end` bucket-range bounds on
            # joins are not supported — use the ON condition over AGG_TIMESTAMP.
            if per is None:
                raise SiddhiAppCreationError(
                    f"joining aggregation {ins.stream_id!r} needs `per '<duration>'`")
            if ins.handlers.window is not None:
                raise SiddhiAppCreationError(
                    "aggregations cannot take windows in joins")
            self.agg_view = self.aggregation.view(per)
            self.attr_types = dict(self.aggregation.output_attr_types)
            self.codec = self.aggregation.output_codec
        elif self.is_table:
            if ins.handlers.window is not None:
                raise SiddhiAppCreationError("tables cannot take windows in joins")
            self.attr_types = dict(self.table.attr_types)
            self.codec = self.table.codec
        elif self.is_named_window:
            if ins.handlers.window is not None:
                raise SiddhiAppCreationError(
                    "named windows cannot take further windows in joins")
            self.attr_types = dict(self.named_window.attr_types)
            self.codec = self.named_window.codec
            # the window's emission stream triggers this side
            self.junction = self.named_window.output_junction
        else:
            self.junction = junctions.get(ins.stream_id)
            if self.junction is None:
                raise DefinitionNotExistError(
                    f"stream {ins.stream_id!r} is not defined")
            self.codec = self.junction.codec
            self.attr_types = {
                a.name: a.type for a in self.junction.definition.attributes
                if a.type != AttributeType.OBJECT}
            from ..ops.windows import make_layout
            layout = make_layout(self.attr_types)
            batch_cap = self.junction.batch_size
            self.window = make_window(
                ins.handlers.window, layout, batch_cap, True, registry,
                annotations=annotations, playback=bool(ctx.playback))
        self.handlers = ins.handlers


def _gather_frame(cols: dict, ts: jax.Array, idx: jax.Array):
    """A frame's columns and stamps at pair lanes `idx`, through ONE `[W, P]`
    gather of its rows packed as the ring packs them (an 8-byte column as
    two words, a float bit-cast): a gather costs by the index, and a pair
    block is `join_pair_cap_factor` batches wide (PERF.md, PR 37)."""
    layout = {k: v.dtype for k, v in cols.items()}
    return _unpack_rows(_pack_rows(cols, ts, layout)[:, idx], layout)


def _named(fn, name: str):
    """`fn` under the name jax gives its program (`jit_<name>`)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class JoinQueryRuntime:
    def __init__(self, query: Query, ctx: SiddhiAppContext,
                 junctions: dict, tables: dict, registry: Registry,
                 name: str, windows: Optional[dict] = None,
                 aggregations: Optional[dict] = None) -> None:
        assert isinstance(query.input_stream, JoinInputStream)
        jis: JoinInputStream = query.input_stream
        self.query = query
        self.ctx = ctx
        self.name = name
        self.registry = registry
        self.callbacks: list[QueryCallback] = []
        self._dropped_dev = None
        self._drop_checks = 0
        self._drop_warned = False
        #: the drop counter as last read from the device (the 64th-batch
        #: sync below, or statistics_report()'s sweep)
        self.dropped_synced = 0
        # statistics_report()["joins"][name]: per probe direction the
        # dispatch of one step, and the drop counter's device sync; the
        # lanes are cumulative over steps, read as deltas
        self.cells = StageCells(("step_left", "step_right", "drop_sync"))
        self._out_lanes = 0
        self._candidate_lanes = 0
        self.output_junction = None
        self.table_executor = None
        self.k_max = dtypes.config.join_max_matches

        self.left = _Side(jis.left, ctx, registry, junctions, tables, windows,
                          aggregations, jis.per, query.annotations)
        self.right = _Side(jis.right, ctx, registry, junctions, tables, windows,
                           aggregations, jis.per, query.annotations)
        if self.left.is_table and self.right.is_table:
            raise SiddhiAppCreationError("cannot join two tables in a stream query")
        if self.left.is_aggregation and self.right.is_aggregation:
            raise SiddhiAppCreationError("cannot join two aggregations")
        if self.left.ref == self.right.ref:
            raise SiddhiAppCreationError(
                "self-joins need an alias: `from S as a join S as b ...`")
        self.join_type = jis.join_type
        self.trigger = jis.trigger
        self.within_ms = jis.within_ms

        # --- resolver over both frames ---
        frames = {self.left.ref: self.left.attr_types,
                  self.right.ref: self.right.attr_types}
        codecs = {self.left.ref: self.left.codec, self.right.ref: self.right.codec}

        def _sp(side):
            # unionSet-projection provenance: junction-fed sides read the
            # upstream output definition's markers; table sides the marker
            # set at wiring time (app_runtime._wire_output)
            if side.is_table:
                return set(getattr(side.table, "set_projection_attrs", ())
                           or ())
            if side.junction is not None:
                return {a.name for a in side.junction.definition.attributes
                        if getattr(a, "set_projection", False)}
            return set()

        set_projections = {ref: sp for ref, sp in
                           ((self.left.ref, _sp(self.left)),
                            (self.right.ref, _sp(self.right))) if sp}
        self.resolver = TypeResolver(frames, self.left.ref, codecs,
                                     set_projections)

        for side in (self.left, self.right):
            side.filters = [compile_expression(f, self.resolver, registry)
                            for f in side.handlers.filters]

        # --- join plans (one per probe direction) ---
        self.plan_from_left = plan_join(jis.on, self.left.ref, self.right.ref,
                                        self.resolver, registry)
        self.plan_from_right = plan_join(jis.on, self.right.ref, self.left.ref,
                                         self.resolver, registry)
        self.index_from_left = self._index_probe(jis.on, self.left,
                                                 self.right, registry)
        self.index_from_right = self._index_probe(jis.on, self.right,
                                                  self.left, registry)

        # --- store-fallback key extraction for cached @store sides ---
        # (reference: AbstractQueryableRecordTable.java:109,207-238 — the
        # cache read path falls back to the store on miss). Per table side,
        # record the simple-attribute equi pairs so on_side_batch can
        # pre-warm the cache with the batch's keys once the store outgrows it.
        from ..io.record_table import RecordTableRuntime
        for t_side, p_side in ((self.left, self.right),
                               (self.right, self.left)):
            t_side._fallback_pairs = None
            t_side._fallback_cond = None
            if (t_side.is_table and isinstance(t_side.table, RecordTableRuntime)
                    and t_side.table.cache_policy is not None):
                pairs = self._simple_equi_pairs(jis.on, p_side, t_side)
                t_side._fallback_pairs = pairs
                if pairs:
                    t_side.table._probe_fallback_ready = True
                else:
                    # non-equi / mixed conditions (`S.k < T.k`): compile the
                    # WHOLE ON condition into a parameterized store
                    # predicate; each probing batch then warms the cache
                    # with every store row matching any probe row
                    # (ensure_cached_for_condition). Conditions the store
                    # walk cannot express (math/functions over table attrs)
                    # keep the documented cache-only miss
                    try:
                        on_rw = _qualify_for_store(
                            jis.on, p_side, t_side, self.resolver)
                        pred = t_side.table.compile_param_condition(on_rw)
                        probe_attrs = sorted({
                            v.attribute
                            for v in collect_vars(on_rw)
                            if v.stream_id == p_side.ref})
                        t_side._fallback_cond = (pred, tuple(probe_attrs))
                        t_side.table._probe_fallback_ready = True
                    except SiddhiAppCreationError:
                        t_side.table._probe_nofallback = True

        # --- selector over the pair frames ---
        select_all = [(n, t) for n, t in self.left.attr_types.items()]
        for n, t in self.right.attr_types.items():
            if n not in dict(select_all):
                select_all.append((n, t))
        self.selector = CompiledSelector(
            query.selector, self.resolver, registry,
            ctx.effective_group_capacity, self.left.ref,
            select_all_attrs=select_all)

        self.output_attributes = tuple(
            Attribute(n, t,
                      set_projection=n in self.selector.host_set_slots)
            for n, t in self.selector.out_types.items())
        self.output_definition = StreamDefinition(
            id=query.output_stream.target_id or f"{name}_out",
            attributes=self.output_attributes)
        self.output_codec = StreamCodec(self.output_definition, ctx.global_strings)

        # --- incremental hash multimaps (one per hashable build side) ---
        # A side's multimap serves probes FROM the other side; it indexes the
        # side's sliding ring by the equi-key hash of the plan that treats it
        # as the build frame. Inserted at append time, probed chain-walk only
        # — no per-step build sort (reference find(): JoinProcessor.java:140).
        def _mm_setup(side, plan_as_build):
            if (isinstance(side.window, SlidingWindow)
                    and plan_as_build.probe_keys):
                return multimap_buckets(side.window.C)
            return None

        self.left._mm_buckets = _mm_setup(self.left, self.plan_from_right)
        self.right._mm_buckets = _mm_setup(self.right, self.plan_from_left)
        self.left._mm_build_keys = self.plan_from_right.build_keys
        self.right._mm_build_keys = self.plan_from_left.build_keys

        def _side_state(s):
            if s.is_table or s.is_named_window or s.is_aggregation:
                return ()
            return s.window.init_state()

        def _mm_state(s):
            if s._mm_buckets is None:
                return ()
            return multimap_init(s.window.C, s._mm_buckets)

        self.state = (
            _side_state(self.left),
            _side_state(self.right),
            _mm_state(self.left),
            _mm_state(self.right),
            self.selector.init_state(),
        )
        # named, so a profiler's `XLA Modules` line tells the join's
        # programs from every other query's `jit_step`
        self._step_left = jax.jit(
            _named(self._make_step(from_left=True), "join_probe_left"),
            donate_argnums=(0,))
        self._step_right = jax.jit(
            _named(self._make_step(from_left=False), "join_probe_right"),
            donate_argnums=(0,))
        from ..ops.windows import window_has_time_semantics
        self.has_time_semantics = any(
            s.window is not None and window_has_time_semantics(s.window)
            for s in (self.left, self.right))

    # ------------------------------------------------------------------- plan

    def _index_probe(self, on, probe_side, build_side, registry):
        """(key expression of the probe side, the key's dtype) where the
        build side is a table on the device index (core/table_index.py) and
        `on` equates its key to a probe-side expression at the top level;
        else None: the sorted probe."""
        table = build_side.table
        key = getattr(table, "index_key", None)
        if key is None or build_side.filters:
            return None
        from .table_index import probe_key
        expr = probe_key(on, table.definition.id, build_side.ref, key,
                         self.resolver, probe_side.ref)
        if expr is None:
            return None
        compiled = compile_expression(expr, self.resolver, registry)
        key_type = table.attr_types[key]
        if compiled.type != key_type and not (
                compiled.type == AttributeType.INT
                and key_type == AttributeType.LONG):
            return None  # a cast could equate unequal values
        return compiled, table.state.cols[key].dtype

    def _simple_equi_pairs(self, on, probe_side, table_side):
        """(probe_attr, table_attr) pairs from `a.x == T.y` conjuncts —
        the shapes the host store fallback can key on. Computed-key equi
        joins (e.g. `f(a.x) == T.y`) get no fallback (documented)."""
        from ..ops.join import frames_of, split_conjuncts
        from ..query_api.expression import Compare, CompareOp, Variable
        pairs = []
        for conj in split_conjuncts(on):
            if not (isinstance(conj, Compare) and conj.op == CompareOp.EQUAL):
                continue
            l, r = conj.left, conj.right
            if not (isinstance(l, Variable) and isinstance(r, Variable)):
                continue
            lf = frames_of(l, self.resolver)
            rf = frames_of(r, self.resolver)
            if lf <= {probe_side.ref} and rf <= {table_side.ref}:
                pairs.append((l.attribute, r.attribute))
            elif lf <= {table_side.ref} and rf <= {probe_side.ref}:
                pairs.append((r.attribute, l.attribute))
        return pairs or None

    def _maybe_store_fallback(self, build, probe, batch: EventBatch) -> None:
        """Pre-warm an overflowed probe cache with this batch's join keys
        (host read-through) so the device probe cannot miss evicted rows.
        Runs BEFORE the step — outer joins then emit nulls only for true
        non-matches, and the selector sees one consistent pass."""
        table = build.table
        pol = getattr(table, "cache_policy", None)
        if pol is None or not pol.overflowed:
            return
        pairs = build._fallback_pairs
        if not pairs:
            if build._fallback_cond is not None:
                self._condition_fallback(build, probe, batch)
            return  # else: PARITY-documented miss warning applies
        valid, host = jax.device_get(
            (batch.valid, {pa: batch.cols[pa] for pa, _ in pairs}))
        import numpy as np
        idx = np.nonzero(valid)[0]
        if idx.size == 0:
            return
        key_cols = []
        for pa, _ta in pairs:
            arr = host[pa][idx]
            at = probe.attr_types[pa]
            if at == AttributeType.STRING:
                key_cols.append(
                    probe.codec.string_tables[pa].decode_array(arr.tolist()))
            elif at == AttributeType.BOOL:
                key_cols.append(arr.astype(bool).tolist())
            else:
                key_cols.append(arr.tolist())
        table.ensure_cached_for_keys(
            tuple(ta for _pa, ta in pairs), set(zip(*key_cols)))

    def _condition_fallback(self, build, probe, batch: EventBatch) -> None:
        """Non-equi / computed probe conditions: warm the cache with every
        store row matching ANY of this batch's probe rows through the
        parameterized store predicate (reference:
        AbstractQueryableRecordTable.java:207-238 — the store is queried
        with streamVariable parameters on every cache miss)."""
        import numpy as np
        pred, probe_attrs = build._fallback_cond
        valid, host = jax.device_get(
            (batch.valid, {a: batch.cols[a] for a in probe_attrs}))
        idx = np.nonzero(valid)[0]
        if idx.size == 0:
            return
        cols = {}
        for a in probe_attrs:
            arr = host[a][idx]
            at = probe.attr_types[a]
            if at == AttributeType.STRING:
                cols[a] = probe.codec.string_tables[a].decode_array(
                    arr.tolist())
            elif at == AttributeType.BOOL:
                cols[a] = arr.astype(bool).tolist()
            else:
                cols[a] = arr.tolist()
        # distinct probe parameter rows, keyed the way walk_condition names
        # stream values ("<probe_ref>.<attr>")
        seen = set()
        param_rows = []
        keys = []
        for i in range(len(idx)):
            t = tuple(cols[a][i] for a in probe_attrs)
            if t in seen:
                continue
            seen.add(t)
            keys.append(t)
            param_rows.append({f"{probe.ref}.{a}": v
                               for a, v in zip(probe_attrs, t)})
        # skip the quadratic store scan for parameter rows already warmed
        # while BOTH the store (rev) and the cache residency (evictions)
        # were unchanged — steady-state probing of a quiet store then costs
        # zero host scans (ADVICE r5); any store write or cache eviction
        # invalidates the memo, falling back to the per-batch scan
        epoch = (build.table._store_rev, build.table.cache_policy.evictions)
        warmed = getattr(build, "_cond_warmed", None)
        if warmed is None or warmed[0] != epoch:
            warmed = (epoch, set())
        fresh = [(t, p) for t, p in zip(keys, param_rows)
                 if t not in warmed[1]]
        if not fresh:
            build._cond_warmed = warmed
            return
        build.table.ensure_cached_for_condition(pred, [p for _, p in fresh])
        # the warm itself may evict (counter moved): re-key so the NEXT
        # batch revalidates; the fresh keys stay memoized under the new
        # epoch only if nothing was displaced
        epoch2 = (build.table._store_rev, build.table.cache_policy.evictions)
        memo = warmed[1] if epoch2 == epoch else set()
        memo.update(t for t, _ in fresh)
        if len(memo) > (1 << 16):  # bounded memo
            memo.clear()
        build._cond_warmed = (epoch2, memo)

    def _probe_outer(self, from_left: bool) -> bool:
        if self.join_type == JoinType.FULL_OUTER:
            return True
        if self.join_type == JoinType.LEFT_OUTER:
            return from_left
        if self.join_type == JoinType.RIGHT_OUTER:
            return not from_left
        return False

    def _make_step(self, from_left: bool):
        probe_side = self.left if from_left else self.right
        build_side = self.right if from_left else self.left
        plan = self.plan_from_left if from_left else self.plan_from_right
        index_probe = (self.index_from_left if from_left
                       else self.index_from_right)
        selector = self.selector
        k_max = self.k_max
        within = self.within_ms
        outer = self._probe_outer(from_left)
        filters = probe_side.filters

        use_mm = (build_side._mm_buckets is not None
                  and not (build_side.is_table or build_side.is_named_window
                           or build_side.is_aggregation)
                  and bool(plan.probe_keys))
        stats = self.ctx.statistics
        qname = self.name

        def step(state, batch: EventBatch, now, build_tstate=None):
            # trace-time: per-query compile counter (see Statistics)
            stats.track_compile(qname, batch.ts.shape[0])
            wl, wr, mml, mmr, sel = state
            w_probe, w_build = (wl, wr) if from_left else (wr, wl)
            mm_probe, mm_build = (mml, mmr) if from_left else (mmr, mml)

            # --- probe-side filter + window append ---
            with stage("filter"):
                pscope = Scope()
                pscope.add_frame(probe_side.ref, batch.cols, batch.ts, batch.valid,
                                 default=True)
                pscope.extras["now"] = now
                mask = batch.valid
                if probe_side.is_named_window:
                    # window emissions carry CURRENT + EXPIRED; only arrivals probe
                    mask = mask & (batch.types == EventType.CURRENT)
                for f in filters:
                    mask = mask & f(pscope)
                batch = dataclasses.replace(batch, valid=mask)
                pscope.valids[probe_side.ref] = mask

            with stage("window"):
                if not (probe_side.is_table or probe_side.is_named_window
                        or probe_side.is_aggregation):
                    appended0 = getattr(w_probe, "appended", None)
                    w_probe, _chunk = probe_side.window.step(w_probe, batch, now)
                    if probe_side._mm_buckets is not None:
                        live = mask & (batch.types == EventType.CURRENT)
                        hashes = _hash_exprs(probe_side._mm_build_keys, pscope)
                        mm_probe = multimap_append(mm_probe, hashes, live,
                                                   appended0)

            if index_probe is not None:
                # one bucket lookup and one row gather a lane: at most one
                # match, the rest of `on` judged on the gathered row
                index, tstate = build_tstate
                key_expr, key_dtype = index_probe
                C_t = tstate.ts.shape[0]
                slot, pv, counts = table_index.probe(
                    index, C_t, key_expr(pscope).astype(key_dtype), mask)
                lane = jax.lax.iota(jnp.int32, batch.ts.shape[0])
                brow = jnp.minimum(slot, C_t - 1)
                b_cols, b_ts = tstate.cols, tstate.ts
                truncated = jnp.int32(0)
            else:
                with stage("probe"):
                    # --- build-side contents (multimap path never materializes
                    #     the full ring — candidates gather packed rows below) ---
                    if use_mm:
                        b_cols = b_ts = b_valid = None
                    elif build_side.is_table:
                        b_cols = build_tstate.cols
                        b_ts = build_tstate.ts
                        b_valid = build_tstate.valid
                    elif build_side.is_named_window:
                        b_cols, b_ts, b_valid = build_side.named_window.contents(
                            build_tstate, now)
                    elif build_side.is_aggregation:
                        b_cols, b_ts, b_valid = build_side.agg_view.contents(
                            build_tstate, now)
                    else:
                        b_cols, b_ts, b_valid = build_side.window.contents(w_build, now)
                    if (not use_mm) and build_side.filters and (
                            build_side.is_table or build_side.is_named_window
                            or build_side.is_aggregation):
                        # stream sides are filtered before their ring append; probed
                        # contents (tables / named windows) are filtered here
                        bscope = Scope()
                        bscope.add_frame(build_side.ref, b_cols, b_ts, b_valid,
                                         default=True)
                        bscope.extras["now"] = now
                        for f in build_side.filters:
                            b_valid = b_valid & f(bscope)

                    # --- candidate pairs ---
                    truncated = jnp.int32(0)
                    if use_mm:
                        bw = build_side.window
                        window_len = w_build.appended - jnp.maximum(
                            w_build.expired, w_build.appended - bw.C)
                        lane, brow, pv, truncated = probe_equi_mm(
                            plan, pscope, mask, mm_build, w_build.appended,
                            window_len, k_max)
                        if bw.time_ms is not None:
                            # probe-time expiry BEFORE pair compaction, mirroring
                            # SlidingWindow.contents(): a time window whose own side
                            # went idle holds rows past their deadline that would
                            # otherwise consume pair_cap slots and evict live matches
                            tsw = w_build.ring[-2:, brow]
                            cand_ts = jax.lax.bitcast_convert_type(
                                jnp.stack([tsw[0], tsw[1]], axis=-1), jnp.int64)
                            pv = pv & (cand_ts + jnp.int64(bw.time_ms) > now)
                    elif plan.probe_keys:
                        lane, brow, pv = probe_equi(
                            plan, pscope, mask, b_cols, b_ts, b_valid,
                            build_side.ref, k_max)
                    else:
                        lane, brow, pv = probe_cross(mask, b_valid, k_max)
            with stage("compact"):
                # compact the sparse [B*k_max] block before any per-pair gather —
                # frame materialization, verification, and the selector then run
                # at ~the real match count instead of k_max x batch. Small blocks
                # keep full width (compaction would only risk truncation there);
                # big blocks cap at factor*B with a monitored drop counter.
                B_probe = batch.ts.shape[0]
                pair_cap = min(lane.shape[0],
                               max(dtypes.config.join_pair_cap_factor * B_probe,
                                   32768))
                if pair_cap < lane.shape[0]:
                    n_matches = jnp.sum(pv, dtype=jnp.int32)
                    dropped = jnp.maximum(n_matches - pair_cap, 0) + truncated
                    lane, brow, pv = compact_pairs(brow, pv, k_max, pair_cap)
                else:
                    dropped = truncated

            # --- pair frames ---
            with stage("frames"):
                if index_probe is not None:
                    p_cols, p_ts = batch.cols, batch.ts  # lane i is lane i
                else:
                    p_cols, p_ts = _gather_frame(batch.cols, batch.ts, lane)
                if use_mm:
                    rows = w_build.ring[:, brow]  # [W, P] packed lane gather
                    g_cols, g_ts = _unpack_rows(rows, build_side.window.layout)
                else:
                    g_cols, g_ts = _gather_frame(b_cols, b_ts, brow)

                pair = Scope()
                if from_left:
                    pair.add_frame(probe_side.ref, p_cols, p_ts, pv, default=True)
                    pair.add_frame(build_side.ref, g_cols, g_ts, pv)
                else:
                    pair.add_frame(build_side.ref, g_cols, g_ts, pv)
                    pair.add_frame(probe_side.ref, p_cols, p_ts, pv, default=True)
                    pair.default_frame = probe_side.ref
                pair.extras["now"] = now

                # --- exact verification: full ON condition + within ---
                if plan.residual is not None:
                    pv = pv & plan.residual(pair)
                if within is not None:
                    pv = pv & (jnp.abs(p_ts - g_ts) <= jnp.int64(within))

                P = lane.shape[0]
                B = batch.ts.shape[0]
                if outer:
                    # unmatched probe lanes join a null build frame
                    matched = jax.ops.segment_max(
                        pv.astype(jnp.int32), lane, num_segments=B) > 0
                    o_valid = mask & ~matched
                    if use_mm:
                        zero_g = {k: jnp.zeros((B,), jnp.dtype(dt))
                                  for k, dt in build_side.window.layout.items()}
                    else:
                        zero_g = {k: jnp.zeros((B,), v.dtype)
                                  for k, v in b_cols.items()}
                    lane = jnp.concatenate([lane, jnp.arange(B)])
                    all_pv = jnp.concatenate([pv, o_valid])
                    has_build = jnp.concatenate(
                        [jnp.ones((P,), bool), jnp.zeros((B,), bool)])
                    p_cols = {k: jnp.concatenate([v, batch.cols[k]])
                              for k, v in p_cols.items()}
                    p_ts = jnp.concatenate([p_ts, batch.ts])
                    g_cols = {k: jnp.concatenate([v, zero_g[k]])
                              for k, v in g_cols.items()}
                    g_ts = jnp.concatenate([g_ts, jnp.zeros((B,), g_ts.dtype)])
                    pv = all_pv
                else:
                    has_build = jnp.ones((P,), bool)

                # zero the build frame on no-build lanes so projections emit nulls
                bf_valid = pv & has_build
                g_cols = {k: jnp.where(bf_valid, v, jnp.zeros((), v.dtype))
                          for k, v in g_cols.items()}

                out_scope = Scope()
                lf_cols, lf_ts = (p_cols, p_ts) if from_left else (g_cols, g_ts)
                rf_cols, rf_ts = (g_cols, g_ts) if from_left else (p_cols, p_ts)
                lf_valid = pv if from_left else bf_valid
                rf_valid = bf_valid if from_left else pv
                out_scope.add_frame(self.left.ref, lf_cols, lf_ts, lf_valid,
                                    default=True)
                out_scope.add_frame(self.right.ref, rf_cols, rf_ts, rf_valid)
                out_scope.extras["now"] = now

            with stage("selector"):
                W = pv.shape[0]
                chunk = EventBatch(
                    ts=p_ts, cols={},
                    valid=pv,
                    types=jnp.zeros((W,), jnp.int8))  # CURRENT
                sel, out = selector.step(sel, chunk, out_scope)

            new_wl, new_wr = (w_probe, w_build) if from_left else (w_build, w_probe)
            new_mml, new_mmr = ((mm_probe, mm_build) if from_left
                                else (mm_build, mm_probe))
            if index_probe is not None:
                return ((new_wl, new_wr, new_mml, new_mmr, sel), out, dropped,
                        counts)
            return (new_wl, new_wr, new_mml, new_mmr, sel), out, dropped

        return step

    # ---------------------------------------------------------------- runtime

    def warmup(self, buckets=None) -> int:
        """AOT-compile both probe directions at their planned batch capacity
        (join steps always receive full-capacity batches — on_side_batch
        pads bucketed deliveries back up) without executing them
        (query_runtime.aot_warm). Returns fresh compiles triggered."""
        from .query_runtime import aot_warm
        n0 = self.ctx.statistics.compiles.get(self.name, 0)
        now = jnp.int64(self.ctx.timestamp_generator.current_time())
        for from_left in (True, False):
            side = self.left if from_left else self.right
            build = self.right if from_left else self.left
            if side.junction is None:
                continue
            triggers = (self.trigger == EventTrigger.ALL
                        or (self.trigger == EventTrigger.LEFT and from_left)
                        or (self.trigger == EventTrigger.RIGHT
                            and not from_left))
            if not triggers:
                continue
            tstate = self._build_state(from_left)
            step = self._step_left if from_left else self._step_right
            batch = EventBatch.empty(side.junction.definition,
                                     side.junction.batch_size)
            aot_warm(step, self.state, batch, now, tstate)
        return self.ctx.statistics.compiles.get(self.name, 0) - n0

    def on_side_batch(self, from_left: bool, batch: EventBatch, now: int) -> None:
        side = self.left if from_left else self.right
        build = self.right if from_left else self.left
        if side.junction is not None and \
                batch.capacity < side.junction.batch_size:
            # join steps are traced at the side's full batch capacity;
            # bucketed junction deliveries widen back (invalid lanes)
            batch = batch.pad_to(side.junction.batch_size)
        triggers = (self.trigger == EventTrigger.ALL
                    or (self.trigger == EventTrigger.LEFT and from_left)
                    or (self.trigger == EventTrigger.RIGHT and not from_left))
        step = self._step_left if from_left else self._step_right
        if build.is_table and (
                getattr(build, "_fallback_pairs", None) is not None
                or getattr(build, "_fallback_cond", None) is not None):
            self._maybe_store_fallback(build, side, batch)
        tstate = self._build_state(from_left)
        if not triggers:
            # non-triggering side still feeds its window (+ multimap)
            if side.is_table or side.is_named_window or side.is_aggregation:
                return
            wl, wr, mml, mmr, sel = self.state
            w = wl if from_left else wr
            mm = mml if from_left else mmr
            w2, mm2 = self._append_only(side, w, mm, batch, now)
            self.state = ((w2, wr, mm2, mmr, sel) if from_left
                          else (wl, w2, mml, mm2, sel))
            return
        # nests in the feeder's `siddhi.feeder.dispatch` (or whichever
        # delivery holds the controller lock)
        side_name = "left" if from_left else "right"
        with self.cells.span("step_" + side_name, "siddhi.join.step",
                             side=side_name):
            self.state, out, dropped, *counts = step(
                self.state, batch, jnp.int64(now), tstate)
        if counts:  # the table index's probes and hits, on the device
            build.table.set_counts(counts[0])
        self._out_lanes += out.capacity
        self._candidate_lanes += batch.capacity * self.k_max
        # accumulate on device; sync only at checkpoints (an int() every
        # batch would serialize the async dispatch pipeline)
        self._dropped_dev = (dropped if self._dropped_dev is None
                             else self._dropped_dev + dropped)
        self._drop_checks += 1
        if not self._drop_warned and self._drop_checks % 64 == 0:
            # a device sync under the controller lock: it waits for every
            # step dispatched so far
            with self.cells.span("drop_sync", "siddhi.join.drop_sync"):
                self.dropped_synced = int(self._dropped_dev)
            if self.dropped_synced > 0:
                import warnings
                warnings.warn(
                    f"join {self.name!r}: {self.dropped_synced} matched "
                    "pairs exceeded the per-step pair block or the per-probe "
                    "candidate walk and were dropped — raise "
                    "config.join_pair_cap_factor / config.join_max_matches",
                    stacklevel=2)
                self._drop_warned = True
        self._distribute(out, now)

    def _build_state(self, from_left: bool):
        """What the step reads of its build side when that side is no
        stream: a table's state (with its index first, where the step
        probes it), a named window's, an aggregation's."""
        build = self.right if from_left else self.left
        if build.is_table:
            if (self.index_from_left if from_left
                    else self.index_from_right) is not None:
                return build.table.index_state(), build.table.state
            return build.table.state
        if build.is_named_window:
            return build.named_window.state
        if build.is_aggregation:
            return build.agg_view.state
        return None

    def stats_snapshot(self) -> dict:
        """statistics_report()["joins"][name]. `steps`, `out_lanes` (the
        out block's lanes, valid or not: what the read-back fetches) and
        `candidate_lanes` (probe lanes x k_max, before compaction) are
        cumulative; `pairs_dropped` is the device counter as last synced."""
        stage_ms = self.cells.snapshot()
        return {
            "steps": {"left": stage_ms["step_left"]["batches"],
                      "right": stage_ms["step_right"]["batches"]},
            "k_max": self.k_max,
            "out_lanes": self._out_lanes,
            "candidate_lanes": self._candidate_lanes,
            "pairs_dropped": self.dropped_synced,
            "stage_ms": stage_ms,
        }

    def _append_only(self, side, wstate, mmstate, batch, now):
        if not hasattr(side, "_append_fn"):
            filters = side.filters

            def fn(w, mm, b, n):
                scope = Scope()
                scope.add_frame(side.ref, b.cols, b.ts, b.valid, default=True)
                scope.extras["now"] = n
                with stage("filter"):
                    mask = b.valid
                    for f in filters:
                        mask = mask & f(scope)
                    b = dataclasses.replace(b, valid=mask)
                with stage("window"):
                    w2, _chunk = side.window.step(w, b, n)
                    if side._mm_buckets is not None:
                        live = mask & (b.types == EventType.CURRENT)
                        hashes = _hash_exprs(side._mm_build_keys, scope)
                        mm = multimap_append(mm, hashes, live, w.appended)
                return w2, mm

            side._append_fn = jax.jit(_named(fn, "join_append"))
        return side._append_fn(wstate, mmstate, batch, jnp.int64(now))

    def _selector_state(self):
        return self.state[4]

    def _distribute(self, out: EventBatch, now: int) -> None:
        from .query_runtime import QueryRuntime
        QueryRuntime._distribute(self, out, now)

    def _select_event_type(self, out, etype):
        from .query_runtime import QueryRuntime
        return QueryRuntime._select_event_type(out, etype)

    def add_callback(self, cb: QueryCallback) -> None:
        self.callbacks.append(cb)


class _JoinSideReceiver(Receiver):
    def __init__(self, runtime: JoinQueryRuntime, from_left: bool):
        self.runtime = runtime
        self.from_left = from_left

    def on_batch(self, batch: EventBatch, now: int) -> None:
        t0 = time.perf_counter_ns()
        compiles = self.runtime.ctx.statistics.compiles
        traced = compiles.get(self.runtime.name, 0)
        self.runtime.on_side_batch(self.from_left, batch, now)
        tele = getattr(self.runtime.ctx, "telemetry", None)
        if tele is not None and tele.on:
            tele.record_query(
                self.runtime.name, time.perf_counter_ns() - t0,
                compiled=compiles.get(self.runtime.name, 0) != traced)
