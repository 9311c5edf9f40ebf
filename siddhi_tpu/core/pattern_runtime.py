"""Pattern / sequence NFA runtime (reference: core/query/input/stream/state/ —
StreamPreStateProcessor.java:46, StreamPostStateProcessor, Logical/Count/Absent
processors, runtimes under state/runtime/; parsed by
StateInputStreamParser.java:73).

The reference walks per-event pending-StateEvent linked lists. The TPU
redesign keeps, per pattern position p, a **fixed-capacity pending table** of
partial matches waiting for position p's event:

    pending[p]:
      frames      {ref: {attr: [P]}}   captured columns of earlier positions
      frame_valid {ref: [P]}           leg/absent frames may be missing
      start_ts    [P]                  first captured event ts (within expiry)
      last_seq    [P]                  arrival seq of latest captured event
      armed_ts    [P]                  when the entry reached this position
      valid       [P]

A micro-batch on a stream junction is matched against every position fed by
that stream **in ascending position order**, so intra-batch chains (A then B
in one batch) complete exactly as the reference's per-event walk would:

    [B,1] arrival frame x [P] pending frames -> [B,P] condition mask
    qualify &= arrival_seq > last_seq   (pattern: skip-till-any-match)
            or arrival_seq == last_seq+1 (sequence: strict contiguity)
    per-entry FIRST qualifying arrival consumes the entry (reference:
    pending state events are removed on match) -> advance or emit.

`every` re-arms position 0 permanently; non-every patterns consume the start
state on first match. `within` invalidates entries by start_ts. Absent
(`not X for T`) entries are killed by a matching X and complete on watermark
`now >= armed_ts + T` (heartbeat-driven — the reference's Scheduler TIMER,
AbsentStreamPreStateProcessor.java:35-57). Logical and/or positions hold two
legs filled in either order. Counts `<m:n>` expand at plan time into n
positions (optional beyond m), with `e[k]`-indexed frames.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..errors import DefinitionNotExistError, SiddhiAppCreationError
from ..extension.registry import Registry
from ..ops.expr_compile import Scope, TypeResolver, compile_expression
from ..ops.keyed_match import first_arrival_by_key, key_words
from ..ops.lanes import gather_lanes, scatter_lanes
from ..ops.search import stable_partition_order
from ..ops.selector import CompiledSelector
from ..query_api.definition import Attribute, AttributeType, StreamDefinition
from ..query_api.execution import (
    AbsentStreamStateElement,
    CountStateElement,
    EveryStateElement,
    LogicalStateElement,
    NextStateElement,
    OutputAction,
    Query,
    SingleInputStream,
    StateInputStream,
    StateType,
    StreamStateElement,
)
from ..query_api.expression import (And, Compare, CompareOp, Expression,
                                    IsNull, Variable)
from ..telemetry.tracing import StageCells, stage
from . import dtypes
from .context import SiddhiAppContext
from .event import EventBatch, EventType, StreamCodec
from .join_runtime import _named
from .query_runtime import QueryCallback
from .stream import Receiver, StreamJunction

BIGSEQ = 2**62  # Python int literal — see ops/windows.py BIG note

#: junction key for the merged multi-stream sequence step
MERGED_SID = "#merged"


@dataclass
class _Leg:
    """One stream condition (a logical position has two)."""

    ref: str
    stream_id: str
    filters: tuple  # Expression ASTs


@dataclass
class _Position:
    index: int
    kind: str  # 'normal' | 'absent' | 'logical'
    legs: list  # [_Leg] (1 normal/absent, 2 logical)
    logical_op: Optional[str] = None  # 'and' | 'or'
    wait_ms: Optional[int] = None  # absent
    optional: bool = False  # count occurrences beyond min_count
    #: mid-pattern `every` (`A -> every B`): matches advance a COPY and the
    #: entry stays armed (reference: EveryInnerStateRuntime re-arming)
    sticky: bool = False

    @property
    def ref(self) -> str:
        return self.legs[0].ref


def _unwrap_chain(elem):
    """EveryStateElement.state may hold a nested ('chain', state, within).
    Returns (inner_element, group_within_ms) — a `within` scoped inside the
    every-group bounds EACH ITERATION (first→last captured event of one
    group traversal), per the reference's per-state within lists
    (StreamPreStateProcessor.java:119-136)."""
    if isinstance(elem, tuple) and elem and elem[0] in ("chain", "seq"):
        return elem[1], elem[2]
    return elem, None


@dataclass
class _EveryGroup:
    """A grouped `every ( ... )` — positions [head, end] form one iteration;
    the NEXT iteration arms only when the current one completes (reference:
    EveryInnerStateRuntime.java:30 re-arms on inner-runtime completion —
    see EveryPatternTestCase testQuery5: A A A A B yields 2 matches, the
    iterations pair up sequentially instead of one per arrival)."""

    head: int
    end: int
    within_ms: Optional[int] = None


class _PatternPlan:
    """Flattens the state AST into a linear position list."""

    def __init__(self, sis: StateInputStream, ctx) -> None:
        self.every = False
        self.positions: list[_Position] = []
        self.is_sequence = sis.state_type == StateType.SEQUENCE
        self.within_ms = sis.within_ms
        #: ref -> (base_ref, occurrence_index) for count groups
        self.count_groups: dict[str, list[str]] = {}
        #: (start_pos, end_pos) spans of zero-minimum count groups, in order
        self.zero_min_spans: list[tuple[int, int]] = []

        #: head every-group (None when the head `every` wraps one element)
        self.head_group: Optional[_EveryGroup] = None
        #: mid-pattern grouped everys, in position order
        self.mid_groups: list[_EveryGroup] = []

        chain = self._linearize(sis.state, top=True)
        first = chain[0]
        if isinstance(first, EveryStateElement):
            self.every = True
            inner, gw = _unwrap_chain(first.state)
            inner_list = self._linearize(inner)
            for e in inner_list:
                self._add_element(e, ctx)
            # a GROUP is a multi-element chain (`every (A -> B)`) or a
            # group-scoped within; a single count element (`every A<2:>`)
            # expands to several positions but keeps per-arrival re-arming
            if len(inner_list) > 1 or gw is not None:
                if self.is_sequence:
                    raise SiddhiAppCreationError(
                        "grouped `every ( ... )` inside a SEQUENCE is not "
                        "supported; use a pattern (`->`) instead")
                self.head_group = _EveryGroup(
                    0, len(self.positions) - 1, gw)
            chain = chain[1:]
        for e in chain:
            if isinstance(e, EveryStateElement):
                inner, gw = _unwrap_chain(e.state)
                inner_list = self._linearize(inner)
                if (len(inner_list) == 1 and gw is None
                        and isinstance(inner_list[0],
                                       (StreamStateElement,
                                        AbsentStreamStateElement))):
                    # mid-pattern every over ONE element: the position
                    # becomes STICKY (matches advance a copy, the entry
                    # stays armed)
                    self._add_element(inner_list[0], ctx)
                    self.positions[-1].sticky = True
                    continue
                # mid-pattern grouped every: `A -> every (B->C) -> D` — the
                # group's head entry stays armed; one iteration in flight
                # at a time, re-armed by each completion
                if gw is not None:
                    raise SiddhiAppCreationError(
                        "`within` scoped inside a MID-pattern `every (...)` "
                        "is not supported; apply within to the whole "
                        "pattern")
                if self.is_sequence:
                    raise SiddhiAppCreationError(
                        "mid-sequence `every` is not supported (strict "
                        "contiguity and re-arming conflict); use a pattern "
                        "(`->`) instead")
                head = len(self.positions)
                if head == 0:
                    raise SiddhiAppCreationError(
                        "`every` on the first element is the head form — "
                        "write `from every ...`")
                for el in inner_list:
                    self._add_element(el, ctx)
                end = len(self.positions) - 1
                for p in self.positions[head:end + 1]:
                    if p.kind != "normal" or p.optional:
                        raise SiddhiAppCreationError(
                            "mid-pattern `every ( ... )` groups support "
                            "plain stream elements only in this build")
                self.mid_groups.append(_EveryGroup(head, end, None))
                continue
            self._add_element(e, ctx)
        if not self.positions:
            raise SiddhiAppCreationError("empty pattern")
        if self.positions[0].kind == "notand":
            raise SiddhiAppCreationError(
                "logical absent (`not X and Y`) as the first pattern element "
                "is not yet supported")
        if self.is_sequence and any(p.kind == "notand"
                                    for p in self.positions):
            raise SiddhiAppCreationError(
                "logical absent (`not X and Y`) inside a SEQUENCE is not "
                "supported (strict contiguity and an open-ended absence "
                "conflict); use a pattern (`->`) instead")
        if self.is_sequence and any(p.sticky for p in self.positions):
            raise SiddhiAppCreationError(
                "mid-sequence `every` is not supported (strict contiguity "
                "and re-arming conflict); use a pattern (`->`) instead")
        if self.positions[0].sticky:
            raise SiddhiAppCreationError(
                "`every` on the first element is the head form — write "
                "`from every e1=... -> ...`")

    def _linearize(self, state, top: bool = False) -> list:
        if isinstance(state, tuple) and state and state[0] in ("chain", "seq"):
            # parenthesized group `( ... ) [within t]`: folding the group's
            # within into the plan is exact only when the group IS the whole
            # pattern — partial-scope withins would wrongly constrain the
            # rest
            _tag, inner, within_ms = state
            if within_ms is not None:
                if not top:
                    raise SiddhiAppCreationError(
                        "`within` on a partial pattern group is not "
                        "supported; apply within to the whole pattern")
                if self.within_ms is not None and self.within_ms != within_ms:
                    raise SiddhiAppCreationError(
                        "conflicting `within` scopes")
                self.within_ms = within_ms
            return self._linearize(inner, top=top)
        if isinstance(state, NextStateElement):
            return (self._linearize(state.state)
                    + self._linearize(state.next))
        return [state]

    def _ref_of(self, stream: SingleInputStream, fallback: str) -> str:
        return stream.alias or fallback

    def _add_element(self, e, ctx) -> None:
        i = len(self.positions)
        if isinstance(e, StreamStateElement):
            s = e.stream
            ref = self._ref_of(s, f"_p{i}")
            self.positions.append(_Position(
                i, "normal",
                [_Leg(ref, s.stream_id, tuple(s.handlers.filters))]))
        elif isinstance(e, AbsentStreamStateElement):
            s = e.stream
            if e.waiting_time_ms is None:
                raise SiddhiAppCreationError(
                    "absent patterns need `for <time>` in this build")
            ref = self._ref_of(s, f"_p{i}")
            self.positions.append(_Position(
                i, "absent",
                [_Leg(ref, s.stream_id, tuple(s.handlers.filters))],
                wait_ms=e.waiting_time_ms))
        elif isinstance(e, LogicalStateElement):
            l, r = e.left, e.right
            # `not X and Y` (either order): the absence holds until the AND
            # partner arrives (reference: LogicalAbsentPatternTestCase;
            # AbsentLogicalPreStateProcessor without a waiting time)
            absent = next((s for s in (l, r)
                           if isinstance(s, AbsentStreamStateElement)), None)
            if absent is not None:
                partner = r if absent is l else l
                if not isinstance(partner, StreamStateElement) or \
                        isinstance(partner, AbsentStreamStateElement):
                    raise SiddhiAppCreationError(
                        "logical absent needs exactly one `not` side and one "
                        "plain stream side")
                if e.logical_type != "and":
                    raise SiddhiAppCreationError(
                        "`not X or Y` is not supported in this build; "
                        "use `not X and Y` or split the query")
                aref = self._ref_of(absent.stream, f"_p{i}a")
                pref = self._ref_of(partner.stream, f"_p{i}b")
                # waiting_time_ms set => timed logical absent
                # (`not X for t and Y`): X within [armed, armed+t) kills;
                # the partner may arrive any time; completion fires at
                # max(armed+t, partner ts) once BOTH hold (reference:
                # AbsentLogicalPreStateProcessor with a waiting time —
                # LogicalAbsentPatternTestCase testQueryAbsent5/5_1/6/7/8)
                self.positions.append(_Position(
                    i, "notand",
                    [_Leg(aref, absent.stream.stream_id,
                          tuple(absent.stream.handlers.filters)),
                     _Leg(pref, partner.stream.stream_id,
                          tuple(partner.stream.handlers.filters))],
                    wait_ms=absent.waiting_time_ms))
                return
            if not (isinstance(l, StreamStateElement)
                    and isinstance(r, StreamStateElement)):
                raise SiddhiAppCreationError(
                    "logical patterns combine two plain stream conditions")
            lref = self._ref_of(l.stream, f"_p{i}a")
            rref = self._ref_of(r.stream, f"_p{i}b")
            self.positions.append(_Position(
                i, "logical",
                [_Leg(lref, l.stream.stream_id, tuple(l.stream.handlers.filters)),
                 _Leg(rref, r.stream.stream_id, tuple(r.stream.handlers.filters))],
                logical_op=e.logical_type))
        elif isinstance(e, CountStateElement):
            s = e.element.stream
            base = self._ref_of(s, f"_p{len(self.positions)}")
            lo = e.min_count
            hi = e.max_count
            if hi == CountStateElement.ANY:
                # UNBOUNDED counts (`A<2:>`, sequence `A+`/`A*`) expand to
                # lo + config.pattern_unbounded_count_extra positions — a
                # DOCUMENTED divergence from the reference's unbounded
                # accumulation (CountPreStateProcessor.java): occurrences
                # past the cap are not captured. Warn loudly at plan time
                # (PARITY.md "Known gaps"); raise the config to widen.
                hi = lo + dtypes.config.pattern_unbounded_count_extra
                import warnings
                warnings.warn(
                    f"unbounded pattern count `{base}<{lo}:>` is expanded "
                    f"to at most {hi} occurrences "
                    "(config.pattern_unbounded_count_extra beyond the "
                    "minimum); occurrences past that are NOT captured — "
                    "raise siddhi_tpu.config.pattern_unbounded_count_extra "
                    "if your matches repeat further", stacklevel=2)
            if lo < 0 or hi < max(lo, 1):
                raise SiddhiAppCreationError(f"bad count range <{lo}:{hi}>")
            refs = []
            span_start = len(self.positions)
            for k in range(hi):
                idx = len(self.positions)
                ref = f"{base}[{k}]"
                refs.append(ref)
                # lo == 0 (`A*` / `A?` / `<0:n>`): every position of the
                # group is optional, so entries epsilon straight through
                # (zero occurrences) and the step's startable-position scan
                # lets the pattern BEGIN past the group
                self.positions.append(_Position(
                    idx, "normal",
                    [_Leg(ref, s.stream_id, tuple(s.handlers.filters))],
                    optional=(lo == 0) or k >= lo))
            self.count_groups[base] = refs
            if lo == 0:
                self.zero_min_spans.append(
                    (span_start, len(self.positions) - 1))
        else:
            raise SiddhiAppCreationError(
                f"unsupported pattern element {type(e).__name__}")


class _RefRewriter:
    """Rewrites e1[0].attr / e1[last].attr / bare count refs onto expanded
    position frames."""

    def __init__(self, count_groups: dict[str, list[str]]):
        self.groups = count_groups

    def rewrite(self, expr):
        if expr is None:
            return None
        if isinstance(expr, Variable):
            sid = expr.stream_id
            if sid in self.groups:
                refs = self.groups[sid]
                if expr.is_last:
                    # e1[last].attr = the newest CAPTURED occurrence, which
                    # varies per match when the count has a range (reference:
                    # CountPreStateProcessor last-event semantics). Compile to
                    # an ifThenElse chain over frame validity, newest first.
                    from ..query_api.expression import (AttributeFunction,
                                                        IsNull, Not)
                    out = Variable(expr.attribute, stream_id=refs[0])
                    for ref in refs[1:]:
                        out = AttributeFunction("", "ifThenElse", (
                            Not(IsNull(stream_id=ref)),
                            Variable(expr.attribute, stream_id=ref),
                            out))
                    return out
                elif expr.stream_index is not None:
                    if expr.stream_index >= len(refs):
                        raise SiddhiAppCreationError(
                            f"{sid}[{expr.stream_index}] exceeds count bound")
                    new_sid = refs[expr.stream_index]
                else:
                    new_sid = refs[0]
                return Variable(expr.attribute, stream_id=new_sid)
            return expr
        kwargs = {}
        for a in ("left", "right", "expression"):
            sub = getattr(expr, a, None)
            if isinstance(sub, Expression):
                kwargs[a] = self.rewrite(sub)
        if hasattr(expr, "parameters") and getattr(expr, "parameters", None):
            return dataclasses.replace(expr, parameters=tuple(
                self.rewrite(p) for p in expr.parameters))
        if kwargs:
            return dataclasses.replace(expr, **kwargs)
        return expr


def pending_capacity_of(query: Query) -> int:
    """A pattern query's pending-table capacity (partial matches held per
    position): `@capacity(pending='N')` on the query, else the process-wide
    `config.pattern_pending_capacity`. analysis/cost.py prices the same."""
    stated = dtypes.stated_capacity(query.annotations).pending
    return dtypes.config.pattern_pending_capacity if stated is None \
        else stated


def _conjuncts(expr) -> list:
    if isinstance(expr, And):
        return _conjuncts(expr.left) + _conjuncts(expr.right)
    return [expr]


def _refs_read(expr, resolver: TypeResolver) -> set:
    """Frame refs an expression reads (None: the resolver's default frame)."""
    if isinstance(expr, Variable):
        return {resolver.resolve(expr)[0]}
    refs: set = set()
    if isinstance(expr, IsNull) and expr.stream_id is not None:
        refs.add(expr.stream_id)
    if dataclasses.is_dataclass(expr):
        for f in dataclasses.fields(expr):
            v = getattr(expr, f.name)
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, Expression):
                    refs |= _refs_read(sub, resolver)
    return refs


@dataclass
class _KeyPlan:
    """A fed position whose condition is `arriving.attr == captured.attr`
    and conjuncts that read the arrival alone: matched by key."""

    arr_attr: str
    cap_ref: str
    cap_attr: str
    residual: list  # compiled conjuncts over the arriving frame


#: attribute types whose `==` is equality of raw device words
_KEY_TYPES = (AttributeType.STRING, AttributeType.INT, AttributeType.LONG,
              AttributeType.BOOL)


def _upstream_order(valid, order) -> jax.Array:
    """Permutation (int32) that puts the valid lanes first, by completing
    event, then by the partial match's age, then by lane: `order` is
    (int32 lane of the completing event in its batch, int64 last seq of
    the entry before it)."""
    comp, prev = order
    lanes = jnp.arange(valid.shape[0], dtype=jnp.int32)
    first = jnp.where(valid, comp, jnp.int32(2 ** 31 - 1))
    return lax.sort((first, prev, lanes), num_keys=3)[-1]


class PendingTable(NamedTuple):
    frames: dict  # {ref: {attr: [P]}}
    frame_valid: dict  # {ref: [P] bool}
    frame_ts: dict  # {ref: [P] int64}
    start_ts: jax.Array  # int64[P]
    last_seq: jax.Array  # int64[P]
    armed_ts: jax.Array  # int64[P]
    valid: jax.Array  # bool[P]
    #: logical positions: per-leg completion. Mid-every GROUP HEAD entries
    #: reuse lane 0 as the iteration-in-flight latch (cleared when the
    #: iteration completes past the group end)
    leg_done: jax.Array  # bool[P, 2]
    #: slot index (in the group head's table) of the context entry that
    #: spawned this in-group iteration entry; -1 outside mid-every groups.
    #: Defaults to None so pre-round-5 snapshots unpickle (restore backfills
    #: from the template — persistence._to_device)
    origin: jax.Array = None  # int32[P]


class PatternState(NamedTuple):
    pending: tuple  # PendingTable per position 1..S-1 (position 0 implicit)
    active0: jax.Array  # bool — start state armed (non-every consumes it)
    seq: jax.Array  # int64 global arrival counter
    sel_state: object
    #: int64 lifetime partial matches dropped: pending-table overflow
    #: (raise config.pattern_pending_capacity) AND sticky-position same-batch
    #: matches past config.pattern_sticky_passes
    dropped: jax.Array
    #: leading-absent arming instant (runtime build time); -2^62 when the
    #: pattern does not start with `not ... for`. Defaults to None so
    #: snapshots pickled before this field existed still unpickle; restore
    #: fills it from the freshly built runtime state (persistence._to_device)
    armed0_ts: jax.Array = None  # int64
    #: head every-group gate: the next iteration may start only with an
    #: arrival seq >= gate0_seq (set to completion seq + 1 when an
    #: iteration finishes — EveryPatternTestCase testQuery5 pairing).
    #: None-default for pre-round-5 snapshot tolerance
    gate0_seq: jax.Array = None  # int64
    #: entries `within` let go, over the query's life (int64), and the high
    #: water of live partial matches over all positions since the last
    #: statistics_report() (int32). None-default like the fields above
    expired: jax.Array = None
    live_hwm: jax.Array = None


class PatternQueryRuntime:
    """Runtime for one pattern/sequence query."""

    def __init__(self, query: Query, ctx: SiddhiAppContext, junctions: dict,
                 tables: dict, registry: Registry, name: str,
                 keyed: bool = True) -> None:
        """`keyed=False` keeps every position on the dense `[B, P]` mask
        (the tests hold the keyed match to it)."""
        assert isinstance(query.input_stream, StateInputStream)
        sis: StateInputStream = query.input_stream
        self.query = query
        self.ctx = ctx
        self.name = name
        self.registry = registry
        self.callbacks: list[QueryCallback] = []
        self.output_junction = None
        self.table_executor = None
        self.tables = tables
        self.P = pending_capacity_of(query)

        self.plan = _PatternPlan(sis, ctx)
        plan = self.plan
        # Multi-stream sequences: strict contiguity needs ONE arrival order
        # across the participating streams (the reference's sequence
        # receivers consume streams in arrival order). Those queries run off
        # a MERGED junction — source junctions are tapped at send() time so
        # true per-event send order survives micro-batching; the merged
        # batch carries a stream tag + each stream's columns under
        # "<sid>::<attr>" names.
        self.merged_mode = False
        self.merged_junction: Optional[StreamJunction] = None
        self._tag_codes: dict[str, int] = {}
        if plan.is_sequence:
            jset = {leg.stream_id for pos in plan.positions for leg in pos.legs}
            self.merged_mode = len(jset) > 1

        # --- junctions / frames / codecs ---
        self.junctions: dict[str, StreamJunction] = {}
        frames: dict[str, dict] = {}
        codecs: dict[str, StreamCodec] = {}
        self.ref_types: dict[str, dict] = {}
        for pos in plan.positions:
            for leg in pos.legs:
                j = junctions.get(leg.stream_id)
                if j is None:
                    raise DefinitionNotExistError(
                        f"stream {leg.stream_id!r} is not defined")
                self.junctions[leg.stream_id] = j
                attr_types = {a.name: a.type for a in j.definition.attributes
                              if a.type != AttributeType.OBJECT}
                frames[leg.ref] = attr_types
                codecs[leg.ref] = j.codec
                self.ref_types[leg.ref] = attr_types
        if self.merged_mode:
            self._build_merged_junction()

        # bare stream names resolve when unambiguous
        sid_count: dict[str, int] = {}
        for pos in plan.positions:
            for leg in pos.legs:
                sid_count[leg.stream_id] = sid_count.get(leg.stream_id, 0) + 1
        for sid, n in sid_count.items():
            if n == 1 and sid not in frames:
                for pos in plan.positions:
                    for leg in pos.legs:
                        if leg.stream_id == sid:
                            frames[sid] = frames[leg.ref]
                            codecs[sid] = codecs[leg.ref]

        rewriter = _RefRewriter(plan.count_groups)
        # unionSet-projection provenance per leg frame (see expr_compile)
        set_projections = {}
        for pos in plan.positions:
            for leg in pos.legs:
                j = self.junctions[leg.stream_id]
                sp = {a.name for a in j.definition.attributes
                      if getattr(a, "set_projection", False)}
                if sp:
                    set_projections[leg.ref] = sp
        self.resolver = TypeResolver(frames, plan.positions[0].legs[0].ref,
                                     codecs, set_projections)

        # --- compile per-leg conditions (unqualified attrs resolve to the
        # leg's own arrival frame, like the reference's per-state meta) ---
        for pos in plan.positions:
            for leg in pos.legs:
                leg_resolver = TypeResolver(frames, leg.ref, codecs,
                                            set_projections)
                leg.compiled = [
                    compile_expression(rewriter.rewrite(f), leg_resolver, registry)
                    for f in leg.filters]
        #: position index -> _KeyPlan, for the positions matched by key
        #: (none with `keyed=False`: the dense mask matches them all)
        self._key_plans: dict[int, _KeyPlan] = {}
        for pos in plan.positions[1:] if keyed else ():
            kp = self._key_plan(pos, rewriter, frames, codecs,
                                set_projections)
            if kp is not None:
                self._key_plans[pos.index] = kp
        self._refuse_oversized_masks()

        # --- selector over all captured frames ---
        select_all = []
        seen = set()
        for pos in plan.positions:
            for leg in pos.legs:
                for n, t in self.ref_types[leg.ref].items():
                    if n not in seen:
                        seen.add(n)
                        select_all.append((n, t))
        sel = query.selector
        sel = dataclasses.replace(
            sel,
            attributes=tuple(dataclasses.replace(a, expression=rewriter.rewrite(a.expression))
                             for a in sel.attributes),
            having=rewriter.rewrite(sel.having),
            group_by=tuple(rewriter.rewrite(g) for g in sel.group_by))
        self.selector = CompiledSelector(
            sel, self.resolver, registry, ctx.effective_group_capacity,
            plan.positions[0].legs[0].ref, select_all_attrs=select_all)

        self.output_attributes = tuple(
            Attribute(n, t,
                      set_projection=n in self.selector.host_set_slots)
            for n, t in self.selector.out_types.items())
        self.output_definition = StreamDefinition(
            id=query.output_stream.target_id or f"{name}_out",
            attributes=self.output_attributes)
        self.output_codec = StreamCodec(self.output_definition, ctx.global_strings)

        # --- state & jitted steps (one per junction + heartbeat) ---
        self.state = self._init_state()
        # named per fed stream, so a profiler's `XLA Modules` line tells the
        # pattern's programs from every other query's `jit_step`
        if self.merged_mode:
            self._steps = {MERGED_SID: jax.jit(
                _named(self._make_step(MERGED_SID), "pattern_step_merged"),
                donate_argnums=(0,))}
        else:
            self._steps = {
                sid: jax.jit(_named(self._make_step(sid),
                                    "pattern_step_" + sid),
                             donate_argnums=(0,))
                for sid in self.junctions
            }
        self._heartbeat_step = jax.jit(
            _named(self._make_step(None), "pattern_heartbeat"),
            donate_argnums=(0,))
        # statistics_report()["patterns"][name]: per fed stream the dispatch
        # of one step, and the drop counter's device sync; the counters are
        # cumulative over steps, read as deltas, and live in the state
        self.cells = StageCells(
            tuple("step_" + sid for sid in self._steps) + ("drop_sync",))
        self._out_lanes = 0
        self._steps_run = 0
        #: as last read from the device: at a report, and the drop counter
        #: at every 64th step too
        self.synced = {"live": 0, "live_hwm": 0, "expired": 0, "dropped": 0}
        self._drop_warned = False
        self.has_time_semantics = (
            plan.within_ms is not None
            or (plan.head_group is not None
                and plan.head_group.within_ms is not None)
            or any(p.kind == "absent" or
                   (p.kind == "notand" and p.wait_ms is not None)
                   for p in plan.positions))

    # ------------------------------------------------------------ keyed match

    def _key_plan(self, pos: _Position, rewriter, frames, codecs,
                  set_projections) -> Optional[_KeyPlan]:
        """The position's key plan if its compiled condition shows one:
        a plain pattern position (one leg; not a sequence, a count, a
        sticky or grouped `every`) with a top-level conjunct
        `arriving.attr == captured.attr` of one attribute type, every other
        conjunct reading the arriving event alone. Anything else stays on
        the dense mask."""
        plan = self.plan
        groups = ([plan.head_group] if plan.head_group else []) \
            + plan.mid_groups
        if (pos.kind != "normal" or pos.sticky or pos.optional
                or plan.is_sequence
                or any(g.head <= pos.index <= g.end for g in groups)):
            return None
        leg = pos.legs[0]
        resolver = TypeResolver(frames, leg.ref, codecs, set_projections)
        own = {None, leg.ref}
        if frames.get(leg.stream_id) is frames[leg.ref]:
            own.add(leg.stream_id)  # the bare stream name, where it is this leg
        captured = set(self._captured_refs(pos.index)) - own
        key, rest = None, []
        for f in leg.filters:
            for c in _conjuncts(rewriter.rewrite(f)):
                sides = None
                if (key is None and isinstance(c, Compare)
                        and c.op == CompareOp.EQUAL
                        and isinstance(c.left, Variable)
                        and isinstance(c.right, Variable)):
                    l, r = resolver.resolve(c.left), resolver.resolve(c.right)
                    if l[0] in captured and r[0] in own:
                        l, r = r, l
                    if (l[0] in own and r[0] in captured and l[2] == r[2]
                            and l[2] in _KEY_TYPES):
                        sides = (l, r)
                if sides is not None:
                    key = sides
                elif _refs_read(c, resolver) <= own:
                    rest.append(c)
                else:
                    return None  # a conjunct reads captured attributes
        if key is None:
            return None
        (_, arr_attr, _), (cap_ref, cap_attr, _) = key
        return _KeyPlan(arr_attr, cap_ref, cap_attr, [
            compile_expression(c, resolver, self.registry) for c in rest])

    def _refuse_oversized_masks(self) -> None:
        """A dense position builds `pred[B, P]` whole (XLA does not fuse it
        away): refuse at build, with the reason, what the first frame would
        die of."""
        stats = jax.devices()[0].memory_stats() or {}
        limit = int(stats.get("bytes_limit", 16 << 30))
        for pos in self.plan.positions[1:]:
            if pos.index in self._key_plans:
                continue
            for leg in pos.legs:
                B = (self.merged_junction if self.merged_mode
                     else self.junctions[leg.stream_id]).batch_size
                if B * self.P > limit:
                    raise SiddhiAppCreationError(
                        f"pattern {self.name!r}: position {pos.index} "
                        f"({leg.ref}) is matched through a dense "
                        f"[{B}, {self.P}] condition mask of "
                        f"{B * self.P:,} bytes, more than the device's "
                        f"{limit:,}; only a condition with a top-level "
                        "`arriving.attr == captured.attr` (other conjuncts "
                        "reading the arriving event alone) is matched by "
                        "key — lower @capacity(pending=...) or the batch "
                        "size, or restate the condition")

    # ---------------------------------------------------------- merged stream

    def _build_merged_junction(self) -> None:
        """One tagged union junction over the sequence's source streams, fed
        by send-order taps so strict contiguity sees the true interleave."""
        participants = []
        for pos in self.plan.positions:
            for leg in pos.legs:
                if leg.stream_id not in participants:
                    participants.append(leg.stream_id)
        self._tag_codes = {sid: i for i, sid in enumerate(participants)}
        attrs = [Attribute("_tag", AttributeType.INT)]
        self._merged_slots: dict[str, tuple[int, list[int]]] = {}
        pad_of = {AttributeType.STRING: "", AttributeType.BOOL: False}
        pads: list = []
        for sid in participants:
            j = self.junctions[sid]
            src_idx = []
            base = len(attrs) - 1  # offset into the padded tail
            for i, a in enumerate(j.definition.attributes):
                if a.type == AttributeType.OBJECT:
                    continue
                attrs.append(Attribute(f"{sid}::{a.name}", a.type))
                src_idx.append(i)
                pads.append(pad_of.get(a.type, 0))
            self._merged_slots[sid] = (base, src_idx)
        merged_def = StreamDefinition(id=f"#seq:{self.name}",
                                      attributes=tuple(attrs))
        self.merged_junction = StreamJunction(merged_def, self.ctx)
        self._merged_pads = tuple(pads)

        for sid in participants:
            code = self._tag_codes[sid]
            base, src_idx = self._merged_slots[sid]
            merged = self.merged_junction

            def tap(ts, data, code=code, base=base, src_idx=src_idx,
                    merged=merged):
                tail = list(self._merged_pads)
                for k, i in enumerate(src_idx):
                    tail[base + k] = data[i]
                # single atomic append (GIL) — taps run on producer threads
                merged.stage_row(ts, (code, *tail))

            self.junctions[sid].taps.append(tap)

    def _leg_batch(self, batch: EventBatch, leg) -> EventBatch:
        """The leg's view of the incoming batch: identity on per-junction
        steps; tag-masked de-prefixed columns on the merged sequence step."""
        if not self.merged_mode:
            return batch
        code = self._tag_codes[leg.stream_id]
        cols = {a: batch.cols[f"{leg.stream_id}::{a}"]
                for a in self.ref_types[leg.ref]}
        valid = batch.valid & (batch.cols["_tag"] == code)
        return EventBatch(ts=batch.ts, cols=cols, valid=valid,
                          types=batch.types)

    # ------------------------------------------------------------------ state

    def _captured_refs(self, pos_index: int) -> list[str]:
        """Frame refs captured before reaching position pos_index (all legs of
        earlier positions)."""
        refs = []
        for pos in self.plan.positions[:pos_index]:
            for leg in pos.legs:
                refs.append(leg.ref)
        # logical (and timed logical-absent) positions also capture their
        # own legs progressively
        pos = self.plan.positions[pos_index]
        if pos.kind == "logical" or (pos.kind == "notand"
                                     and pos.wait_ms is not None):
            for leg in pos.legs:
                refs.append(leg.ref)
        return refs

    def _empty_pending(self, pos_index: int) -> PendingTable:
        P = self.P
        frames = {}
        fvalid = {}
        fts = {}
        for ref in self._captured_refs(pos_index):
            frames[ref] = {
                n: jnp.zeros((P,), dtypes.device_dtype(t))
                for n, t in self.ref_types[ref].items()}
            fvalid[ref] = jnp.zeros((P,), bool)
            fts[ref] = jnp.zeros((P,), dtypes.TS_DTYPE)
        return PendingTable(
            frames=frames, frame_valid=fvalid, frame_ts=fts,
            start_ts=jnp.zeros((P,), dtypes.TS_DTYPE),
            last_seq=jnp.zeros((P,), jnp.int64),
            armed_ts=jnp.zeros((P,), dtypes.TS_DTYPE),
            valid=jnp.zeros((P,), bool),
            leg_done=jnp.zeros((P, 2), bool),
            origin=jnp.full((P,), -1, jnp.int32),
        )

    def _init_state(self) -> PatternState:
        S = len(self.plan.positions)
        leading_absent = self.plan.positions[0].kind == "absent"
        return PatternState(
            pending=tuple(self._empty_pending(p) for p in range(1, S)),
            active0=jnp.bool_(True),
            seq=jnp.int64(0),
            sel_state=self.selector.init_state(),
            dropped=jnp.int64(0),
            armed0_ts=jnp.int64(
                (-1 if self.ctx.playback
                 else self.ctx.timestamp_generator.current_time())
                if leading_absent else -(2 ** 62)),
            gate0_seq=jnp.int64(0),
            expired=jnp.int64(0),
            live_hwm=jnp.int32(0),
        )

    # ------------------------------------------------------------------- step

    def _leg_cond(self, leg, batch: EventBatch, pend: Optional[PendingTable],
                  now) -> jax.Array:
        """[B,P] (or [B,1] for position 0) filter mask for one leg."""
        B = batch.ts.shape[0]
        with stage("filter"):
            scope = Scope()
            cols_b = {k: v[:, None] for k, v in batch.cols.items()}
            scope.add_frame(leg.ref, cols_b, batch.ts[:, None],
                            batch.valid[:, None], default=True)
            # bare stream name alias
            scope.frames.setdefault(leg.stream_id, cols_b)
            scope.valids.setdefault(leg.stream_id, batch.valid[:, None])
            scope.ts.setdefault(leg.stream_id, batch.ts[:, None])
            if pend is not None:
                for ref, cols in pend.frames.items():
                    if ref == leg.ref:
                        # logical positions capture their OWN legs in the
                        # pending table; the leg's frame here must stay the
                        # ARRIVING event, not the (possibly empty) capture —
                        # otherwise a leg filter evaluates against zeros and
                        # never matches
                        continue
                    scope.add_frame(ref, cols, pend.frame_ts[ref],
                                    pend.frame_valid[ref])
            scope.extras["now"] = now
            m = batch.valid[:, None]
            for ce in leg.compiled:
                m = m & ce(scope)
            P = pend.valid.shape[0] if pend is not None else 1
            return jnp.broadcast_to(m, (B, P))

    def _first_by_key(self, kp: _KeyPlan, leg, batch: EventBatch,
                      pend: PendingTable, lane_rank, seq0, now):
        """(found [P], lane [P]): per pending entry the first arriving lane
        that carries its key, passes the conjuncts that read the arrival
        alone, and arrived after the entry's last captured event — what
        `argmin` over the dense mask finds, in (B + P) log (B + P)."""
        B = batch.ts.shape[0]
        scope = Scope()
        scope.add_frame(leg.ref, batch.cols, batch.ts, batch.valid,
                        default=True)
        scope.frames.setdefault(leg.stream_id, batch.cols)
        scope.valids.setdefault(leg.stream_id, batch.valid)
        scope.ts.setdefault(leg.stream_id, batch.ts)
        scope.extras["now"] = now
        with stage("filter"):
            ok = batch.valid
            for ce in kp.residual:
                ok = ok & ce(scope)
        with stage("match"):
            after = jnp.clip(pend.last_seq - seq0, -1,
                             B - 1).astype(jnp.int32)
            return first_arrival_by_key(
                key_words(batch.cols[kp.arr_attr]), ok,
                lane_rank.astype(jnp.int32),
                key_words(pend.frames[kp.cap_ref][kp.cap_attr]), pend.valid,
                after)

    def _make_step(self, junction_sid: Optional[str]):
        plan = self.plan
        selector = self.selector
        stats = self.ctx.statistics
        qname = self.name
        S = len(plan.positions)
        P = self.P
        within = plan.within_ms
        is_seq = plan.is_sequence
        every = plan.every
        playback = bool(self.ctx.playback)

        hg = plan.head_group
        mid_heads = {g.head: g for g in plan.mid_groups}
        # positions where a NEW match may begin: 0, plus the position after
        # each leading zero-minimum count group (`A*, B`: a B with zero A's
        # starts the match at B). Groups (every (...)) exclude themselves.
        startable = {0}
        _idx = 0
        for _s0, _e0 in plan.zero_min_spans:
            if _s0 != _idx:
                break
            _idx = _e0 + 1
            if _idx < S:
                in_group = (hg is not None and _idx <= hg.end) or any(
                    g.head <= _idx <= g.end for g in plan.mid_groups)
                if not in_group:
                    startable.add(_idx)

        def step(state: PatternState, batch: EventBatch, now):
            # trace-time: per-query compile counter (see Statistics)
            stats.track_compile(qname, batch.ts.shape[0])
            pending = list(state.pending)
            active0_box = [state.active0]
            gate0_box = [state.gate0_seq if state.gate0_seq is not None
                         else jnp.int64(0)]
            B = batch.ts.shape[0]

            with stage("filter"):
                n_valid = jnp.sum(batch.valid.astype(jnp.int64))
                # arrival sequence per lane (valid lanes, in lane order)
                lane_rank = jnp.cumsum(batch.valid.astype(jnp.int64)) - 1
                arr_seq = jnp.where(batch.valid, state.seq + lane_rank, BIGSEQ)

            # collected outputs: one block per completion source
            out_blocks = []  # (frames {ref: cols}, fvalid {ref}, fts, ts, valid)
            drop_acc = [jnp.int64(0)]  # pending-table insert overflow
            expired_acc = [jnp.int64(0)]  # entries `within` let go
            armed0_out = [state.armed0_ts]  # leading-absent lazy arming
            gate_ctx = {"active0": active0_box, "gate0": gate0_box}

            def expire(pend: PendingTable, pos_index: int) -> PendingTable:
                gw = (hg.within_ms if hg is not None
                      and hg.head < pos_index <= hg.end else None)
                if within is None and gw is None:
                    return pend
                ok = pend.valid
                if within is not None:
                    ok = ok & (now - pend.start_ts <= jnp.int64(within))
                if gw is not None:
                    # within scoped INSIDE `every (...)`: bounds each
                    # ITERATION (start_ts = the iteration's first capture)
                    ok = ok & (now - pend.start_ts <= jnp.int64(gw))
                died = pend.valid & ~ok
                expired_acc[0] = expired_acc[0] + jnp.sum(
                    died, dtype=jnp.int64)
                if hg is not None and hg.head < pos_index <= hg.end:
                    # the in-flight head-group iteration expired: re-arm
                    # the gate or the every-loop would stall forever
                    active0_box[0] = active0_box[0] | died.any()
                for g in plan.mid_groups:
                    if g.head < pos_index <= g.end:
                        # clear the origin context entry's busy latch
                        P_ = pend.valid.shape[0]
                        o = jnp.where(died & (pend.origin >= 0),
                                      pend.origin, P_)
                        head_tbl = pending[g.head - 1]
                        pending[g.head - 1] = head_tbl._replace(
                            leg_done=head_tbl.leg_done.at[o, 0].set(
                                False, mode="drop"))
                return pend._replace(valid=ok)

            # in place: expire() may clear busy latches on EARLIER tables
            # (mid-every origins), which a rebinding comprehension would
            # discard
            # a position matched by key expires per arrival, against the
            # arriving event's own timestamp (see the match below); the wall
            # clock still sweeps it, the app's playback clock does not: on
            # the served path that one runs ahead with every frame decoded
            with stage("match/expire"):
                for _i in range(len(pending)):
                    if not (playback and (_i + 1) in self._key_plans):
                        pending[_i] = expire(pending[_i], _i + 1)

            merged = junction_sid == MERGED_SID

            def begin_at(pi: int, pos):
                """Start NEW match entries at position pi (pi=0, or a
                startable position past leading zero-min optionals): one
                shared protocol for gate + start-state consumption."""
                leg = pos.legs[0]
                leg_b = self._leg_batch(batch, leg)
                m = self._leg_cond(leg, leg_b, None, now)[:, 0]  # [B]
                with stage("match"):
                    gated = hg is not None and pi == 0
                    if not every or gated:
                        # non-every: only the first match consumes the start
                        # state. Grouped head-every: the gate admits ONE
                        # iteration at a time — the first qualifying arrival
                        # past the previous completion's seq starts it, and
                        # the gate re-opens when the iteration leaves the
                        # group (EveryPatternTestCase testQuery5 pairing)
                        a0 = active0_box[0]
                        if gated:
                            m = m & (arr_seq >= gate0_box[0])
                        mseq = jnp.where(m, arr_seq, BIGSEQ)
                        only = jnp.zeros((B,), bool).at[jnp.argmin(mseq)].set(
                            True)
                        m = m & only & a0
                        active0_box[0] = a0 & ~m.any()
                with stage("frames"):
                    frames = {leg.ref: dict(leg_b.cols)}
                    fvalid = {leg.ref: m}
                    fts = {leg.ref: batch.ts}
                    for pos_e in plan.positions[:pi]:  # skipped zero-min refs
                        for lg in pos_e.legs:
                            frames[lg.ref] = {
                                n: jnp.zeros((B,), dtypes.device_dtype(t))
                                for n, t in self.ref_types[lg.ref].items()}
                            fvalid[lg.ref] = jnp.zeros((B,), bool)
                            fts[lg.ref] = jnp.zeros((B,), dtypes.TS_DTYPE)
                self._advance(pending, out_blocks, pi + 1, frames, fvalid,
                              fts, batch.ts, arr_seq, batch.ts, m, drop_acc,
                              gate_ctx=gate_ctx)

            def process_position(pi: int):
                pos = plan.positions[pi]
                active0 = active0_box[0]
                pend = pending[pi - 1] if pi > 0 else None
                feeds = junction_sid is not None and (merged or any(
                    leg.stream_id == junction_sid for leg in pos.legs))

                # ---- absent completion (time-driven, runs on every step) ----
                if pos.kind == "absent" and pi > 0:
                    with stage("match"):
                        due = pend.valid & (now >= pend.armed_ts +
                                            jnp.int64(pos.wait_ms))
                        killed_late = jnp.zeros_like(pend.valid)
                        if junction_sid is not None and \
                                (merged or pos.legs[0].stream_id == junction_sid):
                            # a matching event kills waiting entries first
                            kill = self._leg_cond(
                                pos.legs[0], self._leg_batch(batch, pos.legs[0]),
                                pend, now)
                            kill = kill & (arr_seq[:, None] > pend.last_seq[None, :])
                            in_period = (batch.ts[:, None] <
                                         pend.armed_ts[None, :] + jnp.int64(pos.wait_ms))
                            killed = (kill & in_period).any(axis=0)
                            # a match PAST the deadline lands in the NEXT (sticky
                            # re-armed) period: the completed period still fires,
                            # then the arming is consumed
                            killed_late = (kill & ~in_period).any(axis=0)
                            pend = pend._replace(valid=pend.valid & ~killed)
                            due = due & ~killed
                        # completions advance with an invalid (absent) frame
                        comp_frames = dict(pend.frames)
                        comp_fvalid = dict(pend.frame_valid)
                        comp_fts = dict(pend.frame_ts)
                        ref = pos.legs[0].ref
                        comp_frames[ref] = {
                            n: jnp.zeros((P,), dtypes.device_dtype(t))
                            for n, t in self.ref_types[ref].items()}
                        comp_fvalid[ref] = jnp.zeros((P,), bool)
                        comp_fts[ref] = jnp.zeros((P,), dtypes.TS_DTYPE)
                        comp_ts = pend.armed_ts + jnp.int64(pos.wait_ms)
                    self._advance(
                        pending, out_blocks, pi + 1,
                        comp_frames, comp_fvalid, comp_fts,
                        jnp.where(pend.valid, pend.start_ts, 0),
                        pend.last_seq, comp_ts, due, drop_acc,
                        origin=pend.origin, gate_ctx=gate_ctx)
                    with stage("match"):
                        if pos.sticky:
                            # `-> every not X for t`: one fire per elapsed quiet
                            # period — re-arm for the next period; a matching
                            # arrival consumes the arming permanently
                            # (EveryAbsentPatternTestCase testQueryAbsent4),
                            # whether it landed in the current period (killed
                            # above) or past its deadline (killed_late). A step
                            # crossing several periods fires once and catches
                            # up on later steps (batch granularity).
                            pend = pend._replace(
                                armed_ts=jnp.where(
                                    due, pend.armed_ts + jnp.int64(pos.wait_ms),
                                    pend.armed_ts),
                                valid=pend.valid & ~killed_late)
                        else:
                            pend = pend._replace(valid=pend.valid & ~due)
                        pending[pi - 1] = pend
                    return

                # ---- timed logical absent: `not X for t and Y` ---------
                # X within [armed, armed+t) kills the entry; the partner Y
                # may arrive before OR after the deadline (captured either
                # way); the match fires at max(armed+t, Y ts) once the
                # period elapses un-killed AND Y is captured (reference:
                # AbsentLogicalPreStateProcessor with waiting time —
                # LogicalAbsentPatternTestCase testQueryAbsent5/5_1/6/7/8).
                # Time-driven completion: runs on every step incl.
                # heartbeats.
                if pos.kind == "notand" and pos.wait_ms is not None \
                        and pi > 0:
                    with stage("match"):
                        a_leg, p_leg = pos.legs
                        Pn = pend.valid.shape[0]
                        deadline = pend.armed_ts + jnp.int64(pos.wait_ms)
                        if junction_sid is not None and (
                                merged or a_leg.stream_id == junction_sid):
                            kq = self._leg_cond(
                                a_leg, self._leg_batch(batch, a_leg), pend, now)
                            kq = kq & (arr_seq[:, None] > pend.last_seq[None, :])
                            kq = kq & (batch.ts[:, None] < deadline[None, :])
                            killed = kq.any(axis=0) & pend.valid
                            pend = pend._replace(valid=pend.valid & ~killed)
                        if junction_sid is not None and (
                                merged or p_leg.stream_id == junction_sid):
                            leg_b = self._leg_batch(batch, p_leg)
                            q = self._leg_cond(p_leg, leg_b, pend, now)
                            q = q & pend.valid[None, :] \
                                & ~pend.leg_done[:, 1][None, :] \
                                & (arr_seq[:, None] > pend.last_seq[None, :])
                            if within is not None:
                                q = q & (batch.ts[:, None]
                                         - pend.start_ts[None, :]
                                         <= jnp.int64(within))
                            qseq = jnp.where(q, arr_seq[:, None], BIGSEQ)
                            b_star = jnp.argmin(qseq, axis=0)
                            matched = q.any(axis=0)
                            cap = {n: v[b_star] for n, v in leg_b.cols.items()}
                            cap_ts = batch.ts[b_star]
                            nf = dict(pend.frames)
                            nfv = dict(pend.frame_valid)
                            nft = dict(pend.frame_ts)
                            nf[p_leg.ref] = {
                                n: jnp.where(matched, cap[n],
                                             pend.frames[p_leg.ref][n])
                                for n in cap}
                            nfv[p_leg.ref] = pend.frame_valid[p_leg.ref] | matched
                            nft[p_leg.ref] = jnp.where(
                                matched, cap_ts, pend.frame_ts[p_leg.ref])
                            pend = pend._replace(
                                frames=nf, frame_valid=nfv, frame_ts=nft,
                                leg_done=pend.leg_done.at[:, 1].set(
                                    pend.leg_done[:, 1] | matched),
                                last_seq=jnp.where(
                                    matched,
                                    jnp.maximum(arr_seq[b_star], pend.last_seq),
                                    pend.last_seq))
                        due = pend.valid & pend.leg_done[:, 1] & (now >= deadline)
                        comp_frames = dict(pend.frames)
                        comp_fv = dict(pend.frame_valid)
                        comp_ft = dict(pend.frame_ts)
                        aref = a_leg.ref
                        comp_frames[aref] = {
                            n: jnp.zeros((Pn,), dtypes.device_dtype(t))
                            for n, t in self.ref_types[aref].items()}
                        comp_fv[aref] = jnp.zeros((Pn,), bool)
                        comp_ft[aref] = jnp.zeros((Pn,), dtypes.TS_DTYPE)
                        comp_ts = jnp.maximum(deadline,
                                              pend.frame_ts[p_leg.ref])
                        new_pend = pend._replace(valid=pend.valid & ~due)
                        pending[pi - 1] = new_pend
                    self._advance(
                        pending, out_blocks, pi + 1,
                        comp_frames, comp_fv, comp_ft,
                        jnp.where(due, pend.start_ts, 0),
                        pend.last_seq, comp_ts, due, drop_acc,
                        origin=pend.origin, gate_ctx=gate_ctx)
                    return

                # ---- leading absent: `not S1 for t -> ...` -------------
                # armed once at runtime build (armed0_ts); a matching
                # arrival before the deadline kills the arming, the
                # deadline passing advances an empty-frame entry to
                # position 1. Granularity: arrivals in the SAME micro-batch
                # as the elapse may match position 1 regardless of their
                # intra-batch order (documented batch-granularity).
                if pos.kind == "absent" and pi == 0:
                    # playback (virtual time) arms LAZILY at the first
                    # observed instant — epoch-timestamp replays must not
                    # measure the quiet period from virtual 0 (which would
                    # both fire spuriously and disarm the kill); realtime
                    # arms at runtime build (reference: query start)
                    with stage("match"):
                        first_ts = jnp.min(jnp.where(
                            batch.valid, batch.ts, jnp.int64(2 ** 62)))
                        armed0 = jnp.where(
                            state.armed0_ts >= 0, state.armed0_ts,
                            jnp.minimum(first_ts, now))
                        deadline = armed0 + jnp.int64(pos.wait_ms)
                        km_any = jnp.bool_(False)
                        km_late_any = jnp.bool_(False)
                        kill_ts = jnp.int64(-(2 ** 62))
                        if junction_sid is not None and (
                                merged or pos.legs[0].stream_id == junction_sid):
                            leg0 = pos.legs[0]
                            km_all = self._leg_cond(
                                leg0, self._leg_batch(batch, leg0), None,
                                now)[:, 0]
                            km = km_all & (batch.ts < deadline)
                            km_any = km.any()
                            # a match past the deadline breaks the NEXT period
                            # (the completed one still fires below); measurement
                            # restarts from the latest matching arrival
                            km_late_any = (km_all & ~(batch.ts < deadline)).any()
                            kill_ts = jnp.max(jnp.where(
                                km_all, batch.ts, jnp.int64(-(2 ** 62))))
                        due = active0 & ~km_any & (now >= deadline)
                        ref = pos.legs[0].ref
                        ins_valid = jnp.zeros((P,), bool).at[0].set(due)
                        frames = {ref: {
                            n: jnp.zeros((P,), dtypes.device_dtype(t))
                            for n, t in self.ref_types[ref].items()}}
                        fvalid = {ref: jnp.zeros((P,), bool)}
                        fts = {ref: jnp.zeros((P,), dtypes.TS_DTYPE)}
                    self._advance(
                        pending, out_blocks, 1, frames, fvalid, fts,
                        jnp.full((P,), deadline),
                        jnp.full((P,), state.seq - 1),
                        jnp.full((P,), deadline), ins_valid, drop_acc,
                        gate_ctx=gate_ctx)
                    with stage("match"):
                        if every:
                            # `every not X for t -> ...`: perpetual quiet-period
                            # monitor (EveryAbsentPatternTestCase testQueryAbsent5
                            # — one entry advances per elapsed period) — re-arm
                            # at each fired boundary; a matching arrival (in the
                            # current period OR past its deadline) restarts
                            # measurement from its own timestamp
                            armed0 = jnp.where(
                                km_any | km_late_any, kill_ts,
                                jnp.where(due, deadline, armed0))
                        else:
                            active0_box[0] = active0 & ~km_any & ~due
                        armed0_out[0] = armed0
                    return

                if not feeds:
                    return

                # ---- normal / logical positions fed by this junction ----
                if pi == 0:
                    # virtual empty pending: [B,1]
                    if pos.kind == "logical":
                        raise SiddhiAppCreationError(
                            "logical conditions at the first pattern position "
                            "are not yet supported")
                    if not merged and pos.legs[0].stream_id != junction_sid:
                        return
                    begin_at(pi, pos)
                    return

                # ---- logical absent: `not X and Y` ---------------------
                # the absence holds until the AND partner arrives: an X
                # earlier than the first qualifying Y kills the entry, a Y
                # earlier than any X advances it (absent frame rides empty,
                # reference AbsentLogicalPreStateProcessor without a timer)
                if pos.kind == "notand":
                    with stage("match"):
                        pend = pending[pi - 1]
                        Pn = pend.valid.shape[0]
                        a_leg, p_leg = pos.legs
                        kseq = jnp.full((Pn,), BIGSEQ)
                        if merged or a_leg.stream_id == junction_sid:
                            kq = self._leg_cond(
                                a_leg, self._leg_batch(batch, a_leg), pend, now)
                            kq = kq & (arr_seq[:, None] > pend.last_seq[None, :])
                            kseq = jnp.min(jnp.where(kq, arr_seq[:, None],
                                                     BIGSEQ), axis=0)
                        pseq = jnp.full((Pn,), BIGSEQ)
                        b_star = jnp.zeros((Pn,), jnp.int64)
                        leg_b = None
                        if merged or p_leg.stream_id == junction_sid:
                            leg_b = self._leg_batch(batch, p_leg)
                            q = self._leg_cond(p_leg, leg_b, pend, now)
                            q = q & pend.valid[None, :] & (
                                arr_seq[:, None] > pend.last_seq[None, :])
                            if within is not None:
                                q = q & (batch.ts[:, None] - pend.start_ts[None, :]
                                         <= jnp.int64(within))
                            qs = jnp.where(q, arr_seq[:, None], BIGSEQ)
                            b_star = jnp.argmin(qs, axis=0)
                            pseq = jnp.min(qs, axis=0)
                        advanced = pend.valid & (pseq < kseq)
                        killed = pend.valid & (kseq < BIGSEQ) & ~advanced
                    if leg_b is not None:
                        with stage("frames"):
                            cap = {n: v[b_star] for n, v in leg_b.cols.items()}
                            cap_ts = batch.ts[b_star]
                        with stage("match"):
                            ins_frames = dict(pend.frames)
                            ins_fvalid = dict(pend.frame_valid)
                            ins_fts = dict(pend.frame_ts)
                            ins_frames[p_leg.ref] = cap
                            ins_fvalid[p_leg.ref] = advanced
                            ins_fts[p_leg.ref] = cap_ts
                            ins_frames[a_leg.ref] = {
                                n: jnp.zeros((Pn,), dtypes.device_dtype(t))
                                for n, t in self.ref_types[a_leg.ref].items()}
                            ins_fvalid[a_leg.ref] = jnp.zeros((Pn,), bool)
                            ins_fts[a_leg.ref] = jnp.zeros((Pn,),
                                                           dtypes.TS_DTYPE)
                            pending[pi - 1] = pend._replace(
                                valid=pend.valid & ~(advanced | killed))
                        self._advance(
                            pending, out_blocks, pi + 1,
                            ins_frames, ins_fvalid, ins_fts,
                            jnp.where(advanced, pend.start_ts, 0),
                            jnp.where(advanced,
                                      jnp.maximum(pseq, pend.last_seq),
                                      pend.last_seq),
                            cap_ts, advanced, drop_acc,
                            origin=pend.origin, gate_ctx=gate_ctx)
                    else:
                        with stage("match"):
                            pending[pi - 1] = pend._replace(
                                valid=pend.valid & ~killed)
                    return

                def _joint_kill(pi=pi, pos=pos):
                    # strict kill computed JOINTLY over both legs (the next
                    # arrival may legitimately match EITHER remaining leg);
                    # re-run before every leg pass so a breaker that becomes
                    # "next" after an in-batch leg match is still caught
                    with stage("match"):
                        pend = pending[pi - 1]
                        q_any = jnp.zeros(
                            (B, pend.valid.shape[0]), bool)
                        for lj, lg in enumerate(pos.legs):
                            if not merged and lg.stream_id != junction_sid:
                                continue
                            ql = self._leg_cond(lg, self._leg_batch(batch, lg),
                                                pend, now)
                            q_any = q_any | (ql & ~pend.leg_done[None, :, lj])
                        nxt = (arr_seq[:, None] == pend.last_seq[None, :] + 1) \
                            & batch.valid[:, None]
                        killed = (nxt & ~q_any).any(axis=0) & pend.valid
                        pending[pi - 1] = pend._replace(
                            valid=pend.valid & ~killed)

                #: ordering snapshot for pattern-mode logical legs — sibling
                #: matches in this batch must not block the other leg's
                #: earlier arrival (legs complete in either order)
                if (pi in startable and pi > 0 and pos.kind == "normal"
                        and (merged
                             or pos.legs[0].stream_id == junction_sid)):
                    # zero-occurrence leading optionals: this arrival may
                    # BEGIN a match here (skipped refs ride as absent
                    # frames, like the reference's unsatisfied optional
                    # count states)
                    begin_at(pi, pos)

                pend0 = pending[pi - 1]
                mid_g = mid_heads.get(pi)
                key_plan = self._key_plans.get(pi)
                leg_iters = list(enumerate(pos.legs))
                if is_seq and pos.kind == "logical":
                    # two passes: with strict contiguity, the second leg's
                    # arrival only becomes reachable (last_seq+1) after the
                    # first leg matched — which may happen later in THIS
                    # batch when arrivals came in the opposite leg order
                    leg_iters = leg_iters * 2
                if pos.sticky:
                    # sticky (mid-pattern every): each pass advances one
                    # more qualifying arrival per entry; arrivals beyond
                    # the pass bound in ONE batch are counted into
                    # `dropped` (monitored; cross-batch repetition is exact)
                    leg_iters = leg_iters * dtypes.config.pattern_sticky_passes
                for li, leg in leg_iters:
                    if is_seq and pos.kind == "logical":
                        _joint_kill()
                    if not merged and leg.stream_id != junction_sid:
                        continue
                    pend = pending[pi - 1]
                    leg_b = self._leg_batch(batch, leg)
                    if key_plan is not None:
                        # matched by key: the entry's first arrival that
                        # satisfies the condition takes it. `within` as
                        # upstream's isExpired: EVERY arrival at the position
                        # first lets go of the entries older than the bound
                        # against its own timestamp — so an entry lives to its
                        # match (or through the batch) only if the largest
                        # stamp among the arrivals up to there is within the
                        # bound of its start
                        found, b_star = self._first_by_key(
                            key_plan, leg, leg_b, pend, lane_rank,
                            state.seq, now)
                        # the arrival's row, by ONE packed gather (its
                        # sequence as the 32-bit rank: a word less)
                        with stage("frames"):
                            row = (leg_b.cols, batch.ts,
                                   lane_rank.astype(jnp.int32))
                        if within is not None:
                            # (not lax.cummax: of an int64 it takes the
                            # TPU's compiler 200 s, this scan 3)
                            with stage("match/expire"):
                                newest = lax.associative_scan(
                                    jnp.maximum, jnp.where(
                                        batch.valid, batch.ts,
                                        jnp.int64(-(2 ** 62))))
                            row += (newest,)
                        with stage("frames"):
                            cap, cap_ts, comp_rank, *seen = gather_lanes(
                                row, b_star)
                        with stage("match"):
                            comp_seq = state.seq + comp_rank.astype(jnp.int64)
                            matched = found
                        if within is not None:
                            with stage("match/expire"):
                                died = pend.valid & (
                                    jnp.where(found, seen[0], newest[-1])
                                    - pend.start_ts > jnp.int64(within))
                                expired_acc[0] = expired_acc[0] + jnp.sum(
                                    died, dtype=jnp.int64)
                                matched = found & ~died
                                pend = pend._replace(valid=pend.valid & ~died)
                        ins_frames = dict(pend.frames)
                        ins_fvalid = dict(pend.frame_valid)
                        ins_fts = dict(pend.frame_ts)
                        ins_frames[leg.ref] = cap
                        ins_fvalid[leg.ref] = matched
                        ins_fts[leg.ref] = cap_ts
                        with stage("match"):
                            pending[pi - 1] = pend._replace(
                                valid=pend.valid & ~matched)
                        self._advance(
                            pending, out_blocks, pi + 1,
                            ins_frames, ins_fvalid, ins_fts,
                            jnp.where(matched, pend.start_ts, 0),
                            jnp.where(matched,
                                      jnp.maximum(comp_seq, pend.last_seq),
                                      pend.last_seq),
                            cap_ts, matched, drop_acc,
                            origin=pend.origin, gate_ctx=gate_ctx,
                            order=(b_star.astype(jnp.int32), pend.last_seq))
                        continue
                    with stage("match"):
                        q = self._leg_cond(leg, leg_b, pend, now)  # [B,P]
                        q = q & pend.valid[None, :]
                        if mid_g is not None:
                            # mid-every group head: an entry with an iteration
                            # in flight (busy latch) does not start another —
                            # re-armed when the iteration completes past the
                            # group end (_advance gate hook)
                            q = q & ~pend.leg_done[:, 0][None, :]
                        if is_seq:
                            q = q & (arr_seq[:, None] == pend.last_seq[None, :] + 1)
                        elif pos.kind == "logical":
                            q = q & (arr_seq[:, None] > pend0.last_seq[None, :])
                        else:
                            q = q & (arr_seq[:, None] > pend.last_seq[None, :])
                        if within is not None:
                            q = q & (batch.ts[:, None] - pend.start_ts[None, :]
                                     <= jnp.int64(within))

                        if is_seq and pos.kind != "logical":
                            # strict: an arrival at seq == last_seq+1 that does NOT
                            # match kills the entry
                            nxt = (arr_seq[:, None] == pend.last_seq[None, :] + 1) \
                                & batch.valid[:, None]
                            killed = (nxt & ~q).any(axis=0)
                            pend = pend._replace(valid=pend.valid & ~killed)
                            q = q & pend.valid[None, :]

                        # first qualifying arrival per entry
                        qseq = jnp.where(q, arr_seq[:, None], BIGSEQ)
                        b_star = jnp.argmin(qseq, axis=0)  # [P]
                        matched = q.any(axis=0)

                    with stage("frames"):
                        cap = {n: v[b_star] for n, v in leg_b.cols.items()}
                        cap_ts = batch.ts[b_star]

                    with stage("match"):
                        if pos.kind == "logical":
                            other = 1 - li
                            # logical positions persist their legs in their own
                            # pending table (both legs are captured refs)
                            new_frames = dict(pend.frames)
                            new_fvalid = dict(pend.frame_valid)
                            new_fts = dict(pend.frame_ts)
                            new_frames[leg.ref] = {
                                n: jnp.where(matched, cap[n],
                                             pend.frames[leg.ref][n])
                                for n in cap}
                            new_fvalid[leg.ref] = pend.frame_valid[leg.ref] | matched
                            new_fts[leg.ref] = jnp.where(
                                matched, cap_ts, pend.frame_ts[leg.ref])
                            complete = (
                                matched if pos.logical_op == "or"
                                else (matched & pend.leg_done[:, other]))
                            pend = pend._replace(
                                frames=new_frames, frame_valid=new_fvalid,
                                frame_ts=new_fts,
                                leg_done=pend.leg_done.at[:, li].set(
                                    pend.leg_done[:, li] | matched),
                                last_seq=jnp.where(
                                    matched,
                                    jnp.maximum(arr_seq[b_star], pend.last_seq),
                                    pend.last_seq))
                            adv_valid = complete
                            ins_frames = pend.frames
                            ins_fvalid = pend.frame_valid
                            ins_fts = pend.frame_ts
                            consumed = complete
                            comp_ts = jnp.where(matched, cap_ts, pend.armed_ts)
                            pending[pi - 1] = pend._replace(
                                valid=pend.valid & ~consumed)
                        else:
                            # carry captured frames + the new arrival frame into
                            # the advance; pend's own structure is untouched
                            ins_frames = dict(pend.frames)
                            ins_fvalid = dict(pend.frame_valid)
                            ins_fts = dict(pend.frame_ts)
                            ins_frames[leg.ref] = cap
                            ins_fvalid[leg.ref] = matched
                            ins_fts[leg.ref] = cap_ts
                            adv_valid = matched
                            comp_ts = cap_ts
                            if pos.sticky:
                                # the entry stays armed; bumping last_seq lets
                                # the next pass advance the NEXT arrival
                                pending[pi - 1] = pend._replace(
                                    last_seq=jnp.where(
                                        matched,
                                        jnp.maximum(arr_seq[b_star],
                                                    pend.last_seq),
                                        pend.last_seq))
                            elif mid_g is not None:
                                # group-head context entry stays armed but
                                # busy-latched until this iteration completes
                                pending[pi - 1] = pend._replace(
                                    leg_done=pend.leg_done.at[:, 0].set(
                                        pend.leg_done[:, 0] | matched),
                                    last_seq=jnp.where(
                                        matched,
                                        jnp.maximum(arr_seq[b_star],
                                                    pend.last_seq),
                                        pend.last_seq))
                            else:
                                pending[pi - 1] = pend._replace(
                                    valid=pend.valid & ~matched)

                    with stage("match"):
                        adv_origin = (
                            jnp.arange(pend.valid.shape[0], dtype=jnp.int32)
                            if mid_g is not None else pend.origin)
                    self._advance(
                        pending, out_blocks, pi + 1,
                        ins_frames, ins_fvalid, ins_fts,
                        jnp.where(adv_valid, pend.start_ts, 0),
                        jnp.where(adv_valid,
                                  jnp.maximum(arr_seq[b_star], pend.last_seq),
                                  pend.last_seq),
                        comp_ts, adv_valid, drop_acc,
                        origin=adv_origin, gate_ctx=gate_ctx,
                        order=(b_star.astype(jnp.int32), pend0.last_seq))

                if pos.sticky and (merged or
                                   pos.legs[0].stream_id == junction_sid):
                    # qualifying arrivals beyond the per-batch pass bound:
                    # counted as dropped (monitored truncation; raise
                    # config.pattern_sticky_passes or shrink batches)
                    with stage("match"):
                        pend = pending[pi - 1]
                        leg0 = pos.legs[0]
                        q_left = self._leg_cond(
                            leg0, self._leg_batch(batch, leg0), pend, now)
                        q_left = q_left & pend.valid[None, :] & (
                            arr_seq[:, None] > pend.last_seq[None, :])
                        if within is not None:
                            # arrivals outside the within window could never
                            # match — they are not truncation
                            q_left = q_left & (
                                batch.ts[:, None] - pend.start_ts[None, :]
                                <= jnp.int64(within))
                        drop_acc[0] = drop_acc[0] + jnp.sum(
                            q_left, dtype=jnp.int64)

            pi = 0
            while pi < S:
                g = hg if (hg is not None and pi == hg.head) else \
                    mid_heads.get(pi)
                if g is not None:
                    # every-group: several passes so iterations can chain
                    # start -> complete -> re-arm -> start within ONE
                    # micro-batch (bounded by pattern_sticky_passes;
                    # leftovers land in the `dropped` monitor below)
                    for _pass in range(dtypes.config.pattern_sticky_passes):
                        for pj in range(g.head, g.end + 1):
                            process_position(pj)
                    # iteration starts beyond the pass bound are LOST for
                    # this batch (events are not buffered): count them into
                    # the monitored `dropped` so operators see the
                    # truncation and can raise pattern_sticky_passes
                    head_pos = plan.positions[g.head]
                    leg0 = head_pos.legs[0]
                    if junction_sid is not None and (
                            merged or leg0.stream_id == junction_sid):
                        with stage("match"):
                            if g is hg:
                                m_left = self._leg_cond(
                                    leg0, self._leg_batch(batch, leg0), None,
                                    now)[:, 0]
                                m_left = m_left & (arr_seq >= gate0_box[0]) \
                                    & batch.valid
                                cnt = jnp.sum(m_left, dtype=jnp.int64)
                                # the in-flight iteration's own start event is
                                # not a leftover (gate closed => one started)
                                cnt = jnp.maximum(
                                    cnt - jnp.where(active0_box[0],
                                                    jnp.int64(0), jnp.int64(1)),
                                    0)
                                drop_acc[0] = drop_acc[0] + cnt
                            else:
                                pend_h = pending[g.head - 1]
                                ql = self._leg_cond(
                                    leg0, self._leg_batch(batch, leg0), pend_h,
                                    now)
                                ql = ql & pend_h.valid[None, :] & (
                                    arr_seq[:, None] > pend_h.last_seq[None, :])
                                if within is not None:
                                    ql = ql & (
                                        batch.ts[:, None]
                                        - pend_h.start_ts[None, :]
                                        <= jnp.int64(within))
                                drop_acc[0] = drop_acc[0] + jnp.sum(
                                    ql, dtype=jnp.int64)
                    pi = g.end + 1
                else:
                    process_position(pi)
                    pi += 1

            # ---- merge output blocks through the selector ----
            new_sel, out = self._emit(state.sel_state, out_blocks, now)
            with stage("append"):
                live = sum((jnp.sum(t.valid, dtype=jnp.int32) for t in pending),
                           jnp.int32(0))
                new_state = PatternState(
                    pending=tuple(pending),
                    active0=active0_box[0],
                    seq=state.seq + n_valid,
                    sel_state=new_sel,
                    dropped=state.dropped + drop_acc[0],
                    armed0_ts=armed0_out[0],
                    gate0_seq=gate0_box[0],
                    expired=expired_acc[0] + (
                        0 if state.expired is None else state.expired),
                    live_hwm=live if state.live_hwm is None else jnp.maximum(
                        state.live_hwm, live),
                )
            return new_state, out

        return step

    # ------------------------------------------------------- pending inserts

    def _advance(self, pending: list, out_blocks: list, target_pos: int,
                 frames, fvalid, fts, start_ts, last_seq, armed_ts,
                 valid, drop_acc=None, origin=None, gate_ctx=None,
                 order=None) -> None:
        """Move completed entries to `target_pos` (insert into its waiting
        table, or emit if past the last position). Optional count positions
        add an epsilon edge: entries also advance past them immediately
        (reference: CountPreStateProcessor forwards once min counts are met).
        Note: the epsilon copy and the stay-behind copy are independent
        entries; a documented round-1 divergence is that both may eventually
        complete (the reference consumes the shared state event once).

        `origin` carries the spawning context slot for mid-every-group
        iteration entries; `gate_ctx` lets group-boundary crossings re-arm
        their every-group (head gate scalars / mid busy latches).

        `order` = (completing event's lane in its batch, the entry's own
        last seq before it), per candidate lane: upstream walks its pending list
        in arrival order for each arriving event, so candidates leave (into
        the next table's free slots, or out of the query) by completing
        event, then by the partial match's age. None: lane order is that
        order already (arrivals starting a match)."""
        S = len(self.plan.positions)
        P = self.P
        if origin is None:
            origin = jnp.full(valid.shape, -1, jnp.int32)
        while True:
            with stage("match"):
                if gate_ctx is not None:
                    hg = self.plan.head_group
                    if hg is not None and target_pos == hg.end + 1:
                        # head every-group completion: re-open the gate for
                        # arrivals past the completing event
                        # (EveryPatternTestCase testQuery4/5)
                        any_c = valid.any()
                        mx = jnp.max(jnp.where(valid, last_seq,
                                               jnp.int64(-BIGSEQ))) + 1
                        gate_ctx["active0"][0] = gate_ctx["active0"][0] | any_c
                        gate_ctx["gate0"][0] = jnp.where(
                            any_c, jnp.maximum(gate_ctx["gate0"][0], mx),
                            gate_ctx["gate0"][0])
                    for g in self.plan.mid_groups:
                        if target_pos == g.end + 1:
                            # mid every-group completion: clear the origin
                            # context entry's busy latch and advance its seq
                            # watermark (testQuery6 sequential iterations)
                            head_tbl = pending[g.head - 1]
                            o = jnp.where(valid & (origin >= 0), origin, P)
                            pending[g.head - 1] = head_tbl._replace(
                                leg_done=head_tbl.leg_done.at[o, 0].set(
                                    False, mode="drop"),
                                last_seq=head_tbl.last_seq.at[o].max(
                                    last_seq, mode="drop"))
                            origin = jnp.full(valid.shape, -1, jnp.int32)
            if target_pos >= S:
                out_blocks.append((frames, fvalid, fts, armed_ts, valid,
                                   order))
                return
            pending[target_pos - 1], n_drop = self._insert_entries(
                pending[target_pos - 1], frames, fvalid, fts,
                start_ts, last_seq, armed_ts, valid, origin, order,
                uses_legs=self._uses_legs(target_pos))
            if drop_acc is not None:
                drop_acc[0] = drop_acc[0] + n_drop
            if not self.plan.positions[target_pos].optional:
                return
            target_pos += 1

    def _uses_legs(self, pos_index: int) -> bool:
        """Whether the position's own table ever sets a `leg_done` flag."""
        pos = self.plan.positions[pos_index]
        return (pos.kind == "logical"
                or (pos.kind == "notand" and pos.wait_ms is not None)
                or any(g.head == pos_index for g in self.plan.mid_groups))

    def _insert_entries(self, dst: PendingTable, frames, fvalid, fts,
                        start_ts, last_seq, armed_ts, valid,
                        origin=None, order=None,
                        uses_legs: bool = True) -> PendingTable:
        """Insert candidate entries into dst's free slots, lowest slot
        first, in `order` (see _advance; lane order without it)."""
        with stage("append"):
            P = self.P
            free_order = stable_partition_order(~dst.valid)
            n_free = jnp.sum((~dst.valid).astype(jnp.int32))
            if order is None:
                rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
            else:
                lanes = jnp.arange(valid.shape[0], dtype=jnp.int32)
                perm = _upstream_order(valid, order)
                rank = jnp.zeros_like(lanes).at[perm].set(
                    lanes, unique_indices=True)
            fits = valid & (rank < n_free)
            n_drop = jnp.sum(valid & ~fits, dtype=jnp.int64)
            # a free slot of its own per fitting lane, P (dropped) for the rest:
            # no two in-bounds slots are equal, as scatter_lanes asks
            slot = jnp.where(fits, free_order[jnp.clip(rank, 0, P - 1)], P)

            new_frames = {}
            new_fvalid = {}
            new_fts = {}
            for ref in dst.frames:
                src_cols = frames.get(ref)
                if src_cols is None:
                    new_frames[ref] = dst.frames[ref]
                    new_fvalid[ref] = dst.frame_valid[ref]
                    new_fts[ref] = dst.frame_ts[ref]
                    continue
                new_frames[ref] = {
                    n: scatter_lanes(dst.frames[ref][n], slot, src_cols[n])
                    for n in dst.frames[ref]}
                new_fvalid[ref] = dst.frame_valid[ref].at[slot].set(
                    fvalid.get(ref, valid), mode="drop")
                new_fts[ref] = scatter_lanes(
                    dst.frame_ts[ref], slot,
                    fts.get(ref, 0))
            if origin is None:
                origin = jnp.full(valid.shape, -1, jnp.int32)
            return PendingTable(
                frames=new_frames, frame_valid=new_fvalid, frame_ts=new_fts,
                start_ts=scatter_lanes(dst.start_ts, slot, start_ts),
                last_seq=scatter_lanes(dst.last_seq, slot, last_seq),
                armed_ts=scatter_lanes(dst.armed_ts, slot, armed_ts),
                valid=dst.valid.at[slot].set(valid, mode="drop"),
                # only a table whose position fills legs or latches (logical,
                # timed not-and, a mid-every group's head) ever sets a flag: the
                # others' stay all False, and a [P, 2] scatter is not free
                leg_done=dst.leg_done.at[slot].set(
                    jnp.zeros((slot.shape[0], 2), bool), mode="drop")
                if uses_legs else dst.leg_done,
                origin=dst.origin.at[slot].set(origin.astype(jnp.int32),
                                               mode="drop"),
            ), n_drop

    # ------------------------------------------------------------------ emit

    def _emit(self, sel_state, out_blocks, now):
        selector = self.selector
        all_refs = []
        for pos in self.plan.positions:
            for leg in pos.legs:
                all_refs.append(leg.ref)

        if not out_blocks:
            # empty output
            with stage("frames"):
                W = 1
                scope = Scope()
                for ref in all_refs:
                    cols = {n: jnp.zeros((W,), dtypes.device_dtype(t))
                            for n, t in self.ref_types[ref].items()}
                    scope.add_frame(ref, cols, jnp.zeros((W,), dtypes.TS_DTYPE),
                                    jnp.zeros((W,), bool),
                                    default=(ref == all_refs[0]))
                self._alias_bare_streams(scope)
                scope.extras["now"] = now
                chunk = EventBatch(ts=jnp.zeros((W,), dtypes.TS_DTYPE), cols={},
                                   valid=jnp.zeros((W,), bool),
                                   types=jnp.zeros((W,), jnp.int8))
            with stage("selector"):
                return selector.step(sel_state, chunk, scope)

        # concatenate blocks lane-wise
        with stage("frames"):
            scope = Scope()
            tss = jnp.concatenate([b[3] for b in out_blocks])
            valids = jnp.concatenate([b[4] for b in out_blocks])
            # upstream's order (see _advance). Blocks that carry none (arrivals
            # completing a one-position pattern, timer completions) come first,
            # in lane order; a step of such blocks alone is in order as it is
        perm = None
        with stage("match"):
            if any(b[5] is not None for b in out_blocks):
                comps, prevs = [], []
                for b in out_blocks:
                    W = b[4].shape[0]
                    comp, prev = b[5] if b[5] is not None else (
                        jnp.full((W,), -1, jnp.int32), jnp.zeros((W,), jnp.int64))
                    comps.append(comp)
                    prevs.append(prev)
                perm = _upstream_order(valids, (jnp.concatenate(comps),
                                                jnp.concatenate(prevs)))
        # a select list that reads each lane alone (no aggregate, order by
        # or limit) commutes with the permutation: permute its few output
        # columns instead of every captured frame
        after = perm is not None and not (
            selector.has_aggregators or selector.group_vars
            or selector.emit_final_per_group or selector.order_by
            or selector.limit is not None or selector.offset is not None)
        with stage("frames"):
            for ref in all_refs:
                cols_parts = []
                valid_parts = []
                ts_parts = []
                for frames, fvalid, fts, ts, v, _ in out_blocks:
                    W = ts.shape[0]
                    if ref in frames:
                        cols_parts.append(frames[ref])
                        valid_parts.append(fvalid[ref] & v)
                        ts_parts.append(fts[ref])
                    else:
                        cols_parts.append({
                            n: jnp.zeros((W,), dtypes.device_dtype(t))
                            for n, t in self.ref_types[ref].items()})
                        valid_parts.append(jnp.zeros((W,), bool))
                        ts_parts.append(jnp.zeros((W,), dtypes.TS_DTYPE))
                cols = {n: jnp.concatenate([c[n] for c in cols_parts])
                        for n in self.ref_types[ref]}
                fv = jnp.concatenate(valid_parts)
                # zero missing frames so projections emit nulls
                cols = {n: jnp.where(fv, v, jnp.zeros((), v.dtype))
                        for n, v in cols.items()}
                fts_all = jnp.concatenate(ts_parts)
                if perm is not None and not after:
                    cols, fts_all, fv = jax.tree_util.tree_map(
                        lambda a: a[perm], (cols, fts_all, fv))
                scope.add_frame(ref, cols, fts_all, fv,
                                default=(ref == all_refs[0]))
            self._alias_bare_streams(scope)
            scope.extras["now"] = now
            if perm is not None and not after:
                tss, valids = tss[perm], valids[perm]
            chunk = EventBatch(ts=tss, cols={}, valid=valids,
                               types=jnp.zeros((tss.shape[0],), jnp.int8))
        with stage("selector"):
            new_sel, out = selector.step(sel_state, chunk, scope)
        with stage("emit"):
            if after:
                out = gather_lanes(out, perm)
        return new_sel, out

    def _alias_bare_streams(self, scope: Scope) -> None:
        """Let unambiguous bare stream names resolve to their position frame."""
        sid_refs: dict[str, list[str]] = {}
        for pos in self.plan.positions:
            for leg in pos.legs:
                sid_refs.setdefault(leg.stream_id, []).append(leg.ref)
        for sid, refs in sid_refs.items():
            if len(refs) == 1 and sid not in scope.frames:
                ref = refs[0]
                scope.frames[sid] = scope.frames[ref]
                scope.valids[sid] = scope.valids[ref]
                scope.ts[sid] = scope.ts[ref]

    # ---------------------------------------------------------------- runtime

    def _feed_junction(self, sid: str) -> StreamJunction:
        return (self.merged_junction if sid == MERGED_SID
                else self.junctions[sid])

    def on_junction_batch(self, sid: str, batch: EventBatch, now: int) -> None:
        cap = self._feed_junction(sid).batch_size
        if batch.capacity < cap:
            # pattern steps bake lane math on the planned capacity; widen
            # bucketed deliveries back (new lanes invalid)
            batch = batch.pad_to(cap)
        # nests in the feeder's `siddhi.feeder.dispatch` (or whichever
        # delivery holds the controller lock)
        with self.cells.span("step_" + sid, "siddhi.pattern.step",
                             stream=sid):
            self.state, out = self._steps[sid](self.state, batch,
                                               jnp.int64(now))
        self._count(out)
        self._distribute(out, now)

    def _count(self, out: EventBatch) -> None:
        """Book one step. The counters stay on the device, in the state, but
        the drop counter at every 64th step, as the join's (an `int()` every
        batch would serialise the async dispatch pipeline)."""
        self._out_lanes += out.capacity
        self._steps_run += 1
        if not self._drop_warned and self._steps_run % 64 == 0:
            # a device sync under the controller lock: it waits for every
            # step dispatched so far
            with self.cells.span("drop_sync", "siddhi.pattern.drop_sync"):
                self.synced["dropped"] = int(self.state.dropped)
            if self.synced["dropped"] > 0:
                import warnings
                warnings.warn(
                    f"pattern {self.name!r}: {self.synced['dropped']} "
                    "partial matches found the pending table full (or a "
                    "sticky position's passes spent) and were dropped — "
                    "raise @capacity(pending=...) on the query",
                    stacklevel=2)
                self._drop_warned = True

    def device_counters(self, report: bool = False) -> dict:
        """Copies of the state's counters for collect_overflow()'s one fetch
        (under the controller lock: the next step donates the state);
        `sync_counters` takes the values back. `report`: the caller is
        statistics_report(), at which the high water starts anew."""
        st = self.state
        live = sum((jnp.sum(t.valid, dtype=jnp.int32) for t in st.pending),
                   jnp.int32(0))
        held = {"live": live, "live_hwm": st.live_hwm,
                "expired": st.expired, "dropped": st.dropped}
        if report:
            self.state = st._replace(live_hwm=live)
        return {k: jnp.copy(v) for k, v in held.items() if v is not None}

    def sync_counters(self, fetched: dict) -> None:
        self.synced.update({k: int(v) for k, v in fetched.items()})

    def stats_snapshot(self) -> dict:
        """statistics_report()["patterns"][name]. `steps`, `out_lanes` (the
        out block's lanes, valid or not: what the read-back fetches) and
        `expired` are cumulative; `live` and `live_hwm` (since the
        statistics_report() before) count partial matches over all positions;
        `pending_dropped` is the device counter as last synced."""
        stage_ms = self.cells.snapshot()
        return {
            "steps": {sid: stage_ms["step_" + sid]["batches"]
                      for sid in self._steps},
            "pending_capacity": self.P,
            "positions_by_key": sorted(self._key_plans),
            "out_lanes": self._out_lanes,
            "live": self.synced["live"],
            "live_hwm": self.synced["live_hwm"],
            "expired": self.synced["expired"],
            "pending_dropped": self.synced["dropped"],
            "stage_ms": stage_ms,
        }

    def warmup(self, buckets=None) -> int:
        """AOT-compile every per-junction step (+ the heartbeat step when
        time semantics need it) at the planned capacity without executing
        (query_runtime.aot_warm)."""
        from .query_runtime import aot_warm
        n0 = self.ctx.statistics.compiles.get(self.name, 0)
        now = jnp.int64(self.ctx.timestamp_generator.current_time())
        for sid, step in self._steps.items():
            j = self._feed_junction(sid)
            empty = EventBatch.empty(j.definition, j.batch_size)
            aot_warm(step, self.state, empty, now)
        if self.has_time_semantics:
            any_j = next(iter(self.junctions.values()))
            empty = EventBatch.empty(any_j.definition, any_j.batch_size)
            aot_warm(self._heartbeat_step, self.state, empty, now)
        return self.ctx.statistics.compiles.get(self.name, 0) - n0

    def heartbeat(self, now: int) -> None:
        if not self.has_time_semantics:
            return
        any_j = next(iter(self.junctions.values()))
        empty = EventBatch.empty(any_j.definition, any_j.batch_size)
        self.state, out = self._heartbeat_step(self.state, empty,
                                               jnp.int64(now))
        self._count(out)
        self._distribute(out, now)

    def _selector_state(self):
        return self.state.sel_state

    def _distribute(self, out: EventBatch, now: int) -> None:
        from .query_runtime import QueryRuntime
        QueryRuntime._distribute(self, out, now)

    def _select_event_type(self, out, etype):
        from .query_runtime import QueryRuntime
        return QueryRuntime._select_event_type(out, etype)

    def add_callback(self, cb: QueryCallback) -> None:
        self.callbacks.append(cb)


class _PatternSideReceiver(Receiver):
    def __init__(self, runtime: PatternQueryRuntime, sid: str):
        self.runtime = runtime
        self.sid = sid

    def on_batch(self, batch: EventBatch, now: int) -> None:
        t0 = time.perf_counter_ns()
        compiles = self.runtime.ctx.statistics.compiles
        traced = compiles.get(self.runtime.name, 0)
        self.runtime.on_junction_batch(self.sid, batch, now)
        tele = getattr(self.runtime.ctx, "telemetry", None)
        if tele is not None and tele.on:
            tele.record_query(
                self.runtime.name, time.perf_counter_ns() - t0,
                compiled=compiles.get(self.runtime.name, 0) != traced)
