"""Compiled SELECT engine (reference: core/query/selector/QuerySelector.java:44).

Consumes window chunks (typed lanes CURRENT/EXPIRED/RESET) and produces an
output EventBatch of projected attributes, reproducing per-event semantics:

- aggregator components update per-key via grouped scans with signed deltas
  (CURRENT=+1, EXPIRED=-1, RESET=epoch bump), emitting the post-update value on
  every lane — exactly QuerySelector.processGroupBy's per-event emission;
- HAVING filters output lanes (QuerySelector.java:228);
- ORDER BY / LIMIT / OFFSET apply per chunk (QuerySelector.java:230-235).

Aggregator calls may be nested inside arbitrary expressions
(`sum(price)/count()`); they are rewritten to references into a synthetic
`__agg__` frame evaluated first.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import dtypes
from ..core.event import EventBatch, EventType
from ..errors import SiddhiAppCreationError
from ..extension.registry import ExtensionKind, Registry
from ..query_api.definition import AttributeType
from ..query_api.execution import OrderByOrder, Selector
from ..query_api.expression import (
    And,
    AttributeFunction,
    Compare,
    Expression,
    In,
    IsNull,
    MathExpression,
    Not,
    Or,
    Variable,
)
from .aggregators import AggregatorFactory, AggregatorSpec
from .expr_compile import CompiledExpr, Scope, TypeResolver, compile_expression
from .groupby import (
    GroupState,
    KeyTable,
    grouped_scan,
    grouped_scan_fused,
    hash_columns,
    init_group_state,
    init_key_table,
    key_lookup_or_insert,
    ungrouped_scan,
    ungrouped_scan_fused,
)

AGG_FRAME = "__agg__"
#: pseudo-column carrying each output lane's group slot (grouped snapshot
#: rate limiting); never part of the output schema
GROUP_SLOT_COL = "__slot__"


def _rewrite_set_idioms(expr: Expression) -> Expression:
    """`sizeOfSet(unionSet(createSet(x)))` (reference:
    UnionSetAttributeAggregatorExecutor + CreateSet/SizeOfSet function
    executors) compiles to an EXACT distinct count on device — the set is
    never materialized. Raw set emission stays host-opaque and is rejected
    at plan time with guidance (see the unionSet registry entry)."""
    if isinstance(expr, AttributeFunction):
        if not expr.namespace and expr.name == "sizeOfSet" and expr.parameters:
            inner = expr.parameters[0]
            if (isinstance(inner, AttributeFunction) and not inner.namespace
                    and inner.name == "unionSet" and inner.parameters):
                arg = inner.parameters[0]
                if (isinstance(arg, AttributeFunction) and not arg.namespace
                        and arg.name == "createSet" and arg.parameters):
                    arg = arg.parameters[0]
                return AttributeFunction("", "distinctCount",
                                         (_rewrite_set_idioms(arg),))
        return AttributeFunction(
            expr.namespace, expr.name,
            tuple(_rewrite_set_idioms(p) for p in expr.parameters))
    for field in ("left", "right", "expression"):
        sub = getattr(expr, field, None)
        if isinstance(sub, Expression):
            expr = dataclasses.replace(
                expr, **{field: _rewrite_set_idioms(sub)})
    return expr


def _rewrite_aggregators(expr: Expression, registry: Registry, found: list):
    """Replace aggregator AttributeFunction nodes with Variables into the
    __agg__ frame; collect (name, node) into `found`. Mirrors the reference's
    aggregator detection at parse time (ExpressionParser.java:462)."""
    if isinstance(expr, AttributeFunction):
        impl = registry.lookup(ExtensionKind.AGGREGATOR, expr.namespace, expr.name)
        if impl is not None:
            slot_name = f"agg{len(found)}"
            found.append((slot_name, expr))
            return Variable(slot_name, stream_id=AGG_FRAME)
        new_params = tuple(_rewrite_aggregators(p, registry, found)
                           for p in expr.parameters)
        return AttributeFunction(expr.namespace, expr.name, new_params)
    if isinstance(expr, MathExpression):
        return dataclasses.replace(
            expr,
            left=_rewrite_aggregators(expr.left, registry, found),
            right=_rewrite_aggregators(expr.right, registry, found))
    if isinstance(expr, Compare):
        return dataclasses.replace(
            expr,
            left=_rewrite_aggregators(expr.left, registry, found),
            right=_rewrite_aggregators(expr.right, registry, found))
    if isinstance(expr, (And, Or)):
        return dataclasses.replace(
            expr,
            left=_rewrite_aggregators(expr.left, registry, found),
            right=_rewrite_aggregators(expr.right, registry, found))
    if isinstance(expr, Not):
        return dataclasses.replace(
            expr, expression=_rewrite_aggregators(expr.expression, registry, found))
    return expr


@dataclass
class SelectorState:
    """Pytree of selector persistent state.

    `groups` holds, in agg-spec order: bare [K] value arrays for FUSED
    components (plain sum-op — they share `shared_epoch`), GroupState for
    monotone/forever components, and custom pytrees for custom scans."""

    groups: list
    key_table: Optional[KeyTable]
    epoch: jax.Array  # int32
    shared_epoch: Optional[jax.Array] = None  # int32[K] for fused components


jax.tree_util.register_dataclass(SelectorState)


class CompiledSelector:
    """Plans one Selector against an input frame layout."""

    def __init__(
        self,
        selector: Selector,
        resolver: TypeResolver,
        registry: Registry,
        group_capacity: int,
        chunk_frame: str,
        select_all_attrs: Optional[list[tuple[str, AttributeType]]] = None,
        emit_final_per_group: bool = False,
        sliding_window: bool = False,
    ):
        self.registry = registry
        self.group_capacity = group_capacity
        self.chunk_frame = chunk_frame
        self.selector = selector
        #: on-demand (pull) mode: emit one lane per group — the final
        #: aggregate — instead of per-event running values (reference:
        #: FindOnDemandQueryRuntime returns one row per group)
        self.emit_final_per_group = emit_final_per_group
        #: set by the runtime before tracing when a grouped snapshot limiter
        #: needs per-lane group slots (GROUP_SLOT_COL)
        self.expose_group_slot = False

        # --- select list: rewrite aggregators, compile expressions ---
        agg_nodes: list[tuple[str, AttributeFunction]] = []
        attrs = selector.attributes
        if not attrs:
            # select * — project every input attribute
            if select_all_attrs is None:
                raise SiddhiAppCreationError("select * needs input attribute list")
            from ..query_api.execution import OutputAttribute
            attrs = tuple(OutputAttribute(n, Variable(n)) for n, _ in select_all_attrs)
        #: raw-set emission (reference:
        #: UnionSetAttributeAggregatorExecutor.java:71 returns the live Set
        #: object): `select unionSet(x) as s` compiles the LIVE-MULTISET
        #: tracking to an exact distinctCount on device; the query runtime
        #: materializes the set HOST-SIDE at the callback boundary from the
        #: per-code pair table. out name -> __agg__ slot (filled below).
        self.host_set_slots: dict[str, str] = {}
        pre = []
        for i, a in enumerate(attrs):
            e = _rewrite_set_idioms(a.expression)
            if (isinstance(e, AttributeFunction) and not e.namespace
                    and e.name == "unionSet" and e.parameters):
                arg = e.parameters[0]
                if (isinstance(arg, AttributeFunction) and not arg.namespace
                        and arg.name == "createSet" and arg.parameters):
                    arg = arg.parameters[0]
                if a.rename is None:
                    raise SiddhiAppCreationError(
                        "raw unionSet(...) output needs an `as` name")
                if selector.group_by:
                    raise SiddhiAppCreationError(
                        "raw unionSet(...) emission is ungrouped-only on "
                        "this engine (use sizeOfSet(unionSet(...)) for "
                        "grouped counts)")
                if compile_expression(arg, resolver,
                                      registry).type != AttributeType.STRING:
                    raise SiddhiAppCreationError(
                        "raw unionSet(...) emission needs a STRING argument "
                        "(host materialization reads the dictionary-code "
                        "table); use sizeOfSet(unionSet(...)) for counts "
                        "over other types")
                self.host_set_slots[a.rename] = ""  # agg slot filled below
                e = AttributeFunction("", "distinctCount", (arg,))
            pre.append((a.rename, e))
        rewritten = [(name, _rewrite_aggregators(e, registry, agg_nodes))
                     for name, e in pre]
        for name, re_ in rewritten:
            if name in self.host_set_slots:
                assert isinstance(re_, Variable)
                self.host_set_slots[name] = re_.attribute
        #: output slots whose value is generated host-side per event at the
        #: host boundary (UUID — reference UUIDFunctionExecutor); device
        #: lanes carry a placeholder code
        self.host_uuid_slots: list[str] = []
        for i, (name, e) in enumerate(rewritten):
            if (isinstance(e, AttributeFunction) and not e.namespace
                    and e.name == "UUID"):
                self.host_uuid_slots.append(name or f"UUID{i}")

        # --- aggregator specs ---
        self.agg_specs: list[tuple[str, AggregatorSpec, list[CompiledExpr]]] = []
        #: sliding-window true extrema: (slot, 'min'|'max', arg exprs) — the
        #: query runtime computes these as range queries over the window's
        #: arrival-order sequence (reference: Min/MaxAttributeAggregator
        #: processRemove) and injects per-lane values via scope extras
        self.extrema_plan: list[tuple[str, str, list[CompiledExpr]]] = []
        for slot_name, node in agg_nodes:
            factory = registry.require(ExtensionKind.AGGREGATOR, node.namespace, node.name)
            assert isinstance(factory, AggregatorFactory)
            args = [compile_expression(p, resolver, registry) for p in node.parameters]
            spec = factory.make(tuple(a.type for a in args))
            if sliding_window and spec.extrema_op is not None:
                self.extrema_plan.append((slot_name, spec.extrema_op, args))
            self.agg_specs.append((slot_name, spec, args))
        self._extrema_slots = {s for s, _, _ in self.extrema_plan}
        self.has_aggregators = bool(self.agg_specs)

        # grouped extrema need the group hash of both ring rows and chunk
        # lanes (ops/extrema.grouped_sliding_extrema_lanes); defined here so
        # ring-side and lane-side hashing can never diverge
        if self.extrema_plan and selector.group_by:
            gvars = [resolver.resolve(v) for v in selector.group_by]

            def group_hash(scope):
                return hash_columns(
                    [scope.col(ref, attr) for ref, attr, _ in gvars])

            self.extrema_group_hash = group_hash
        else:
            self.extrema_group_hash = None

        # --- resolver extended with the __agg__ frame ---
        frames = dict(resolver.frames)
        frames[AGG_FRAME] = {slot: spec.return_type
                             for slot, spec, _ in self.agg_specs}
        self.resolver = TypeResolver(frames, resolver.default_frame,
                                     resolver.codecs,
                                     resolver.set_projections)

        self.out_exprs: list[tuple[str, CompiledExpr]] = []
        for name, e in rewritten:
            if name in self.host_uuid_slots:
                # placeholder string code; the runtime substitutes uuid4()
                # per event at the host boundary
                self.out_exprs.append((name, CompiledExpr(
                    lambda s: jnp.zeros(
                        s.ts[s.default_frame].shape, jnp.int32),
                    AttributeType.STRING)))
            else:
                self.out_exprs.append(
                    (name, compile_expression(e, self.resolver, registry)))
        self.out_types: dict[str, AttributeType] = {
            name: ce.type for name, ce in self.out_exprs}
        for name in self.host_set_slots:
            # the device lane carries the EXACT distinct count: downstream
            # consumers (insert into T, chained queries) receive the
            # set-size projection as LONG — `sizeOfSet(T.s)` reads it
            # directly (reference forwards the live Set object,
            # UnionSetAttributeAggregatorExecutor.java:71; the size-at-
            # emission projection is the documented divergence,
            # docs/PARITY.md). Query callbacks still substitute the
            # MATERIALIZED host set at the boundary (union_set_values)
            self.out_types[name] = AttributeType.LONG

        # --- group-by key plan ---
        self.group_by = selector.group_by
        self.group_vars = [resolver.resolve(v) for v in selector.group_by]
        self.use_string_code = (
            len(self.group_vars) == 1 and self.group_vars[0][2] == AttributeType.STRING)
        self.needs_key_table = bool(self.group_vars) and not self.use_string_code

        # --- having / order by compiled against the output frame ---
        out_frames = dict(frames)
        out_frames["__out__"] = dict(self.out_types)
        # a string the select passes through keeps its source's codes: a
        # constant compared with it is interned in that source's table
        strings = {}
        for name, e in rewritten:
            if isinstance(e, Variable) \
                    and self.out_types.get(name) == AttributeType.STRING:
                ref, attr, _ = self.resolver.resolve(e)
                codec = resolver.codecs.get(ref or resolver.default_frame)
                if codec is not None and attr in codec.string_tables:
                    strings[name] = codec.string_tables[attr]
        out_codecs = dict(resolver.codecs)
        if strings:
            out_codecs["__out__"] = SimpleNamespace(string_tables=strings)
        out_resolver = TypeResolver(out_frames, "__out__", out_codecs,
                                    resolver.set_projections)
        self.having = (compile_expression(selector.having, out_resolver, registry)
                       if selector.having is not None else None)
        self.order_by = [(out_resolver.resolve(ob.variable), ob.order)
                         for ob in selector.order_by]
        self.limit = selector.limit
        self.offset = selector.offset

    # ------------------------------------------------------------------ state

    def init_state(self) -> SelectorState:
        groups = []
        K = self.group_capacity if self.group_vars else 1
        any_fused = False
        for slot_name, spec, _ in self.agg_specs:
            if slot_name in self._extrema_slots:
                continue  # runtime-computed; no device state
            if spec.custom_scan is not None:
                groups.append(spec.init_custom(
                    self.group_capacity, grouped=bool(self.group_vars)))
                continue
            for comp in spec.components:
                if (comp.op == "sum" and not comp.ignore_removal
                        and not comp.ignore_reset):
                    # fused components: bare values array, shared epoch table
                    groups.append(jnp.zeros((K,), dtype=comp.dtype))
                    any_fused = True
                else:
                    groups.append(init_group_state(K, comp.dtype))
        return SelectorState(
            groups=groups,
            key_table=init_key_table(K) if self.needs_key_table else None,
            epoch=jnp.int32(0),
            shared_epoch=jnp.zeros((K,), jnp.int32) if any_fused else None,
        )

    def union_set_values(self, sstate: "SelectorState", out_name: str,
                         string_table) -> set:
        """Materialize the LIVE value set behind a raw-unionSet output slot
        (ungrouped string fast path: per-code pair counts). One batched
        device fetch; codes decode through the app-global string table."""
        agg_slot = self.host_set_slots[out_name]
        off = 0
        state = None
        for slot_name, spec, _ in self.agg_specs:
            if slot_name in self._extrema_slots:
                continue
            if slot_name == agg_slot:
                state = sstate.groups[off]
                break
            off += 1 if spec.custom_scan is not None else len(spec.components)
        assert state is not None, f"no state for set slot {out_name!r}"
        pair_counts = state[0]  # (pair GroupState[P], distinct GroupState[1])
        vals, ep, cur = jax.device_get(
            (pair_counts.values, pair_counts.epoch, sstate.epoch))
        import numpy as np
        live = np.nonzero((ep == cur) & (vals > 0))[0]
        return {string_table.decode(int(c)) for c in live}

    # ------------------------------------------------------------------- step

    @functools.cached_property
    def lane_sequential(self) -> bool:
        """True where `step` is a fold over the chunk's lanes in order whose
        rows and state come out bit for bit the same however the chunk is
        cut into consecutive calls: no feature reads the chunk as a whole
        (order by, offset, limit, one row per group, the window extrema's
        range queries), no key table (it numbers a call's new keys in slot
        order), and every scan exact under regrouping (integer sums, min and
        max: a float sum rounds by the shape of its scan)."""
        if (self.order_by or self.offset is not None
                or self.limit is not None or self.emit_final_per_group
                or self.extrema_plan or self.needs_key_table):
            return False
        for _, spec, _ in self.agg_specs:
            if spec.custom_scan is not None:
                state = jax.eval_shape(lambda: spec.init_custom(
                    self.group_capacity, grouped=bool(self.group_vars)))
                keyed = any(isinstance(x, KeyTable) for x in jax.tree.leaves(
                    state, is_leaf=lambda x: isinstance(x, KeyTable)))
                if keyed or not spec.lane_sequential:
                    return False
            elif not all(c.op in ("min", "max")
                         or jnp.issubdtype(c.dtype, jnp.integer)
                         or c.dtype == jnp.bool_ for c in spec.components):
                return False
        return True

    def step_in_rounds(self, state: SelectorState, chunk: EventBatch,
                       scope: Scope, width: int):
        """`step` over the chunk's lanes up to its last valid one, in rounds
        of `width` lanes carried through one loop; only where
        `lane_sequential`, so that the rows and the state are `step`'s own.
        At least one round runs. Returns (state, out, lanes run): `out` is
        as wide as the chunk, and its lanes past the last round are invalid
        rows with the chunk's stamps and types."""
        L = chunk.capacity
        padded = -(-L // width) * width

        def pad(a):
            return a if padded == L else jnp.pad(a, (0, padded - L))

        ts, valid, types = pad(chunk.ts), pad(chunk.valid), pad(chunk.types)
        cols = {k: pad(v) for k, v in chunk.cols.items()}
        frames = {ref: ({k: pad(v) for k, v in fc.items()},
                        pad(scope.ts[ref]), pad(scope.valids[ref]))
                  for ref, fc in scope.frames.items()}
        n_live = jnp.max(jnp.where(
            valid, jnp.arange(1, padded + 1, dtype=jnp.int32), 0))
        rounds = jnp.maximum(1, (n_live + width - 1) // width)

        def one_round(r, sstate):
            def cut(a):
                return jax.lax.dynamic_slice_in_dim(a, r * width, width)

            sub = Scope()
            for ref, (fc, fts, fvalid) in frames.items():
                sub.add_frame(ref, {k: cut(v) for k, v in fc.items()},
                              cut(fts), cut(fvalid))
            sub.default_frame = scope.default_frame
            sub.extras = dict(scope.extras)
            part = EventBatch(ts=cut(ts), cols={k: cut(v) for k, v in
                                                cols.items()},
                              valid=cut(valid), types=cut(types))
            return self.step(sstate, part, sub)

        shapes = jax.eval_shape(one_round, 0, state)[1].cols

        def body(r, carry):
            sstate, out_cols, out_valid = carry
            sstate, out = one_round(r, sstate)
            put = functools.partial(jax.lax.dynamic_update_slice_in_dim,
                                    start_index=r * width, axis=0)
            return (sstate, {k: put(v, out.cols[k])
                             for k, v in out_cols.items()},
                    put(out_valid, out.valid))

        state, out_cols, out_valid = jax.lax.fori_loop(
            0, rounds, body,
            (state, {k: jnp.zeros((padded,), s.dtype)
                     for k, s in shapes.items()},
             jnp.zeros((padded,), bool)))
        out = EventBatch(ts=chunk.ts,
                         cols={k: v[:L] for k, v in out_cols.items()},
                         valid=out_valid[:L], types=chunk.types)
        return state, out, rounds * width

    def step(self, state: SelectorState, chunk: EventBatch,
             scope: Scope) -> tuple[SelectorState, EventBatch]:
        L = chunk.capacity
        valid = chunk.valid
        types = chunk.types
        is_current = types == EventType.CURRENT
        is_expired = types == EventType.EXPIRED
        is_reset = valid & (types == EventType.RESET)
        data_valid = valid & (is_current | is_expired)

        new_key_table = state.key_table
        if self.group_vars:
            if self.use_string_code:
                ref, attr, _ = self.group_vars[0]
                slots = scope.col(ref, attr)
            else:
                key_cols = [scope.col(ref, attr) for ref, attr, _ in self.group_vars]
                hashed = hash_columns(key_cols)
                new_key_table, slots, kres = key_lookup_or_insert(
                    state.key_table, hashed, data_valid)
                # unresolved lanes (key table exhausted) must not alias
                # group 0: sentinel slots sort out of every segment scan
                # (monitored truncation via the table's miss counter)
                slots = jnp.where(kres, slots, jnp.int32(self.group_capacity))
        else:
            slots = jnp.zeros((L,), jnp.int32)

        sign = jnp.where(is_expired, -1, 1).astype(jnp.int32)

        # --- run aggregator components ---
        # plain sum-op components (sum/count/avg/stdDev parts) fuse into ONE
        # scan sharing one epoch table; monotone/forever/custom run separately
        new_groups = list(state.groups)
        gi = 0
        results: dict[int, jax.Array] = {}
        pending: list[tuple[str, AggregatorSpec, list[int]]] = []
        fused_idx: list[int] = []
        fused_vals: list = []
        fused_deltas: list = []
        any_reset = is_reset
        no_reset = jnp.zeros((L,), bool)
        extrema_values: dict[str, jax.Array] = {}
        for slot_name, spec, args in self.agg_specs:
            if slot_name in self._extrema_slots:
                # per-lane window extrema computed by the query runtime
                # (range queries over the window's arrival-order sequence)
                extrema_values[slot_name] = scope.extras[
                    f"extrema:{slot_name}"]
                continue
            arg_vals = [a(scope) for a in args] if args else [None]
            if spec.custom_scan is not None:
                g, out_vals = spec.custom_scan(
                    state.groups[gi], slots.astype(jnp.int32), arg_vals,
                    sign, data_valid, any_reset, state.epoch,
                    grouped=bool(self.group_vars))
                new_groups[gi] = g
                results[gi] = out_vals
                pending.append((slot_name, spec, [gi]))
                gi += 1
                continue
            comp_gis = []
            for comp in spec.components:
                deltas = comp.delta(arg_vals[0], sign)
                if (comp.op == "sum" and not comp.ignore_removal
                        and not comp.ignore_reset):
                    fused_idx.append(gi)
                    fused_vals.append(state.groups[gi])
                    fused_deltas.append(deltas)
                else:
                    lane_valid = data_valid if not comp.ignore_removal else (
                        valid & is_current)
                    resets = no_reset if comp.ignore_reset else any_reset
                    if self.group_vars:
                        g, out_vals = grouped_scan(
                            state.groups[gi], slots.astype(jnp.int32), deltas,
                            lane_valid, resets, state.epoch, op=comp.op)
                    else:
                        g, out_vals = ungrouped_scan(
                            state.groups[gi], deltas, lane_valid, resets,
                            state.epoch, op=comp.op)
                    new_groups[gi] = g
                    results[gi] = out_vals
                comp_gis.append(gi)
                gi += 1
            pending.append((slot_name, spec, comp_gis))

        shared_epoch = state.shared_epoch
        if fused_idx and self.group_vars:
            f_vals, shared_epoch, f_outs = grouped_scan_fused(
                fused_vals, state.shared_epoch, slots.astype(jnp.int32),
                fused_deltas, data_valid, any_reset, state.epoch)
            for i, g in zip(fused_idx, f_vals):
                new_groups[i] = g
            for i, o in zip(fused_idx, f_outs):
                results[i] = o
        elif fused_idx:
            f_vals, shared_epoch, f_outs = ungrouped_scan_fused(
                fused_vals, state.shared_epoch, fused_deltas, data_valid,
                any_reset, state.epoch)
            for i, g in zip(fused_idx, f_vals):
                new_groups[i] = g
            for i, o in zip(fused_idx, f_outs):
                results[i] = o

        agg_values: dict[str, jax.Array] = dict(extrema_values)
        for slot_name, spec, comp_gis in pending:
            if spec.custom_scan is not None:
                agg_values[slot_name] = results[comp_gis[0]]
            else:
                agg_values[slot_name] = spec.finalize(
                    [results[i] for i in comp_gis])

        # dtype-stable accumulate: a bare jnp.sum promotes int32->int64
        # under x64, silently changing the state aval between the first and
        # second step — which retriggers a FULL ~seconds-long XLA recompile
        new_epoch = state.epoch + jnp.sum(
            is_reset.astype(jnp.int32), dtype=state.epoch.dtype)

        # --- project output attributes ---
        if self.agg_specs:
            scope.frames[AGG_FRAME] = agg_values
            scope.valids[AGG_FRAME] = data_valid
            scope.ts[AGG_FRAME] = chunk.ts
        # constant-only projections (`select 1.0 as w`) trace to 0-d
        # scalars: broadcast to lane width so downstream decode/table
        # inserts see a proper column
        out_cols = {}
        for name, ce in self.out_exprs:
            v = ce(scope)
            if jnp.ndim(v) == 0:
                v = jnp.broadcast_to(v, chunk.ts.shape)
            out_cols[name] = v
        if self.expose_group_slot:
            # grouped snapshot limiters retain one row per group — ride the
            # per-lane group slot through ordering/limit as a pseudo-column
            out_cols[GROUP_SLOT_COL] = slots.astype(jnp.int32)

        out_valid = data_valid

        if self.emit_final_per_group and self.has_aggregators:
            # keep only the last lane of each group — its running aggregate is
            # the group's final value — BEFORE having, so HAVING judges the
            # final aggregate, not an intermediate running value
            idx = jnp.arange(L, dtype=jnp.int32)
            K = self.group_capacity if self.group_vars else 1
            last = jax.ops.segment_max(
                jnp.where(out_valid, idx, -1), slots.astype(jnp.int32),
                num_segments=K)
            out_valid = out_valid & (idx == last[slots.astype(jnp.int32)])

        # --- having on the output frame ---
        if self.having is not None or self.order_by:
            scope.frames["__out__"] = out_cols
            scope.valids["__out__"] = out_valid
            scope.ts["__out__"] = chunk.ts
        if self.having is not None:
            out_valid = out_valid & self.having(scope)

        out = EventBatch(ts=chunk.ts, cols=out_cols, valid=out_valid, types=types)

        # --- order by / offset / limit (per chunk, like the reference) ---
        if self.order_by:
            out = self._order_chunk(out)
        if self.offset is not None or self.limit is not None:
            out = self._limit_chunk(out)

        return SelectorState(new_groups, new_key_table, new_epoch,
                             shared_epoch), out

    def _order_chunk(self, out: EventBatch) -> EventBatch:
        keys = []
        for (ref, attr, _), order in reversed(self.order_by):
            col = out.cols[attr]
            if order == OrderByOrder.DESC:
                col = -col if jnp.issubdtype(col.dtype, jnp.number) else ~col
            keys.append(col)
        # push invalid lanes to the end, stable within
        perm = jnp.arange(out.capacity)
        for k in keys:
            k = jnp.where(out.valid[perm], k[perm].astype(jnp.float64),
                          jnp.inf)
            perm = perm[jnp.argsort(k, stable=True)]
        # single final ordering: invalid last
        final_key = jnp.where(out.valid[perm], 0, 1)
        perm = perm[jnp.argsort(final_key, stable=True)]
        return EventBatch(
            ts=out.ts[perm],
            cols={k: v[perm] for k, v in out.cols.items()},
            valid=out.valid[perm],
            types=out.types[perm],
        )

    def _limit_chunk(self, out: EventBatch) -> EventBatch:
        rank = jnp.cumsum(out.valid.astype(jnp.int32)) - 1
        keep = out.valid
        if self.offset is not None:
            keep = keep & (rank >= self.offset)
            rank = rank - self.offset
        if self.limit is not None:
            keep = keep & (rank < self.limit)
        return dataclasses.replace(out, valid=keep)
