"""expression / expressionBatch windows via monotone-suffix evaluation.

Reference: core/query/processor/stream/window/ExpressionWindowProcessor.java
(395 LoC) re-evaluates an arbitrary expression after every arrival and pops
events from the FRONT while it is false. Arbitrary re-evaluation is a
per-event interpreter loop; the TPU form restricts the condition to
MONOTONE-SUFFIX shapes — conditions that can only become true by dropping
old events — for which the retained window after each arrival is the largest
valid suffix, and each arrival's expiry frontier is a binary search over
prefix metrics of the arrival sequence:

  count() REL N                  -> frontier = pos + 1 - N
  sum(attr) REL C (attr >= 0)    -> searchsorted over the prefix-sum array
  last.a - first.a REL C         -> searchsorted over the (monotone) values
  eventTimestamp(last) - eventTimestamp(first) REL C -> same on timestamps
  AND of the above               -> max of frontiers

REL is < or <=. Anything else (OR, >, ==, arbitrary attrs) is rejected at
plan time with guidance — matching SURVEY §7's "compiler-friendly control
flow" rule rather than emulating the interpreter loop.

expressionBatch (ExpressionBatchWindowProcessor) keeps accumulating until
the condition would break, then flushes as a batch. Only the count() form
(equivalent to lengthBatch) segments in parallel; the window factory
delegates it and rejects the rest (greedy segmentation by running sums is
inherently sequential).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core import dtypes
from ..core.event import EventBatch, EventType
from ..errors import SiddhiAppCreationError
from ..query_api.expression import (
    And,
    AttributeFunction,
    Compare,
    CompareOp,
    Constant,
    Expression,
    MathExpression,
    MathOp,
    Variable,
)
from .search import searchsorted32
from .windows import (
    KIND_CURRENT,
    KIND_EXPIRED,
    SlidingState,
    sliding_state0,
    WindowOp,
    _layout_words,
    _pack_rows,
    _packed_ts,
    _append_packed,
    _fetch_rel_packed,
    _ring_live_mask,
    _sort_chunk_packed,
    _unpack_rows,
    compact_packed,
)


class _Conjunct(NamedTuple):
    kind: str  # 'count' | 'sum' | 'span' | 'ts_span'
    attr: Optional[str]
    limit: float  # effective inclusive limit (REL folded in)
    strict: bool  # True for '<'


def _first_last_attr(e: Expression) -> Optional[str]:
    """`last.a - first.a` -> 'a'; eventTimestamp(last)-eventTimestamp(first)
    -> '' (the ts payload)."""
    if (isinstance(e, MathExpression) and e.op == MathOp.SUBTRACT):
        l, r = e.left, e.right
        if (isinstance(l, Variable) and isinstance(r, Variable)
                and l.stream_id == "last" and r.stream_id == "first"
                and l.attribute == r.attribute):
            return l.attribute
        if (isinstance(l, AttributeFunction) and isinstance(r, AttributeFunction)
                and l.name == "eventTimestamp" and r.name == "eventTimestamp"
                and l.parameters and r.parameters
                and isinstance(l.parameters[0], Variable)
                and isinstance(r.parameters[0], Variable)
                and l.parameters[0].attribute == "last"
                and r.parameters[0].attribute == "first"):
            return ""
    return None


def plan_expression(expr: Expression, layout: dict) -> list[_Conjunct]:
    """Decompose a window condition into monotone conjuncts or reject."""
    if isinstance(expr, And):
        return plan_expression(expr.left, layout) + \
            plan_expression(expr.right, layout)
    if not isinstance(expr, Compare):
        raise SiddhiAppCreationError(
            f"expression window conditions must be AND-ed comparisons; "
            f"got {type(expr).__name__} — see ops/expression_window.py for "
            "the supported monotone forms")
    left, op, right = expr.left, expr.op, expr.right
    if isinstance(left, Constant) and not isinstance(right, Constant):
        # `10 > count()` == `count() < 10`
        flip = {CompareOp.GREATER_THAN: CompareOp.LESS_THAN,
                CompareOp.GREATER_THAN_EQUAL: CompareOp.LESS_THAN_EQUAL}
        if op not in flip:
            raise SiddhiAppCreationError(
                "expression window conditions must bound a window metric "
                "from above (< / <=): only shrinking the window can restore "
                "them (monotone-suffix evaluation)")
        left, op, right = right, flip[op], left
    if op not in (CompareOp.LESS_THAN, CompareOp.LESS_THAN_EQUAL):
        raise SiddhiAppCreationError(
            "expression window conditions must bound a window metric from "
            "above (< / <=): only shrinking the window can restore them "
            "(monotone-suffix evaluation)")
    if not isinstance(right, Constant):
        raise SiddhiAppCreationError(
            "expression window bounds must be constants")
    limit = float(right.value)
    strict = op == CompareOp.LESS_THAN

    if (isinstance(left, AttributeFunction) and left.name == "count"
            and not left.parameters):
        return [_Conjunct("count", None, limit, strict)]
    if (isinstance(left, AttributeFunction) and left.name == "sum"
            and left.parameters and isinstance(left.parameters[0], Variable)):
        attr = left.parameters[0].attribute
        if attr not in layout:
            raise SiddhiAppCreationError(
                f"expression window sum() over unknown attribute {attr!r}")
        return [_Conjunct("sum", attr, limit, strict)]
    fl = _first_last_attr(left)
    if fl is not None:
        if fl == "":
            return [_Conjunct("ts_span", None, limit, strict)]
        if fl not in layout:
            raise SiddhiAppCreationError(
                f"expression window span over unknown attribute {fl!r}")
        return [_Conjunct("span", fl, limit, strict)]
    raise SiddhiAppCreationError(
        "unsupported expression window term; supported monotone forms: "
        "count(), sum(attr) with non-negative values, "
        "last.attr - first.attr (monotone attr), "
        "eventTimestamp(last) - eventTimestamp(first)")


class ExpressionWindow(WindowOp):
    """Sliding expression window: after each arrival, the retained window is
    the largest suffix satisfying every conjunct. Expiry is arrival-driven
    (the reference also re-evaluates only on events for these forms)."""

    def __init__(self, layout: dict, batch_cap: int, condition: str):
        from ..compiler import parse_expression
        self.layout = layout
        self.B = batch_cap
        self.conjuncts = plan_expression(parse_expression(condition), layout)
        self.C = max(dtypes.config.default_window_capacity, batch_cap)
        # count() bounds are statically known: size the ring so the retained
        # window can never wrap past capacity (mirrors length(N) setting
        # C = max(N, batch_cap); sum/span forms have no static bound and
        # rely on the step's monitored overflow counter instead)
        for conj in self.conjuncts:
            if conj.kind == "count":
                self.C = max(self.C, int(conj.limit) + batch_cap)
        self.E = max(batch_cap, 1024)
        self.C = max(self.C, self.E)
        self.chunk_width = self.B + self.E
        self.W = _layout_words(layout)

    def init_state(self) -> SlidingState:
        return sliding_state0(self.W, self.C)

    def _metric_seq(self, conj: _Conjunct, ring_cols, ring_ts, comp_cols,
                    comp_ts, expired, winlen0, n_valid32, fill):
        """Arrival-order metric values: position r holds the event at overall
        index expired + r; window rows [0, winlen0), then this batch's
        arrivals at [winlen0, winlen0 + n_valid). Dead positions hold `fill`
        (0 for prefix sums, dtype-max to keep span sequences monotone)."""
        C, B = self.C, self.B
        if conj.kind == "ts_span":
            ring_vals, comp_vals = ring_ts, comp_ts
        else:
            ring_vals = ring_cols[conj.attr]
            comp_vals = comp_cols[conj.attr].astype(ring_vals.dtype)
        base = (expired % C).astype(jnp.int32)
        arr = jax.lax.dynamic_slice(
            jnp.concatenate([ring_vals, ring_vals]), (base,), (C,))
        fill = jnp.asarray(fill, arr.dtype)
        arr = jnp.where(jnp.arange(C, dtype=jnp.int32) < winlen0, arr, fill)
        A = jnp.concatenate([arr, jnp.full((B,), fill, arr.dtype)])
        p = jnp.arange(B, dtype=jnp.int32)
        dest = jnp.where(p < n_valid32, winlen0 + p, C + B)
        return A.at[dest].set(comp_vals, mode="drop")

    def _frontiers(self, ring_cols, ring_ts, comp_cols, comp_ts, expired,
                   winlen0, n_valid32, q):
        """Per-arrival expiry frontier via binary searches over prefix
        metrics (the monotone fast path; GeneralExpressionWindow overrides
        this with the exact sequential pop-loop for arbitrary conditions)."""
        B, C = self.B, self.C
        s = jnp.zeros((B,), jnp.int32)
        for conj in self.conjuncts:
            if conj.kind == "count":
                n = int(conj.limit) - (1 if conj.strict else 0)
                if n < 1:
                    raise SiddhiAppCreationError(
                        "expression window count bound admits no events")
                f = q + 1 - jnp.int32(n)
            elif conj.kind == "sum":
                seq = self._metric_seq(conj, ring_cols, ring_ts, comp_cols,
                                       comp_ts, expired, winlen0,
                                       n_valid32, 0)
                # prefix[t] = sum seq[0..t-1]; window [s,q] sum =
                # prefix[q+1] - prefix[s] REL lim -> smallest s with
                # prefix[s] >= (strict: >) prefix[q+1] - lim
                prefix = jnp.concatenate([
                    jnp.zeros((1,), jnp.float64),
                    jnp.cumsum(seq.astype(jnp.float64))])
                tot = prefix[1 + jnp.clip(q, 0, C + B - 1)]
                f = searchsorted32(prefix, tot - conj.limit,
                                   side="right" if conj.strict else "left")
            else:  # span / ts_span over a monotone sequence
                big = (jnp.iinfo(jnp.int64).max
                       if conj.kind == "ts_span" else jnp.inf)
                seq = self._metric_seq(conj, ring_cols, ring_ts, comp_cols,
                                       comp_ts, expired, winlen0,
                                       n_valid32, big)
                lastv = seq[jnp.clip(q, 0, C + B - 1)]
                # need seq[s] >= lastv - lim (strict: > lastv - lim)
                target = lastv - jnp.asarray(conj.limit, seq.dtype)
                f = searchsorted32(seq, target,
                                   side="right" if conj.strict else "left")
            s = jnp.maximum(s, f)
        return s

    def step(self, state: SlidingState, batch: EventBatch, now: jax.Array):
        B, E, C = self.B, self.E, self.C
        comp_mat, n_valid32 = compact_packed(batch, self.layout)
        n_valid = n_valid32.astype(jnp.int64)
        comp_cols, comp_ts = _unpack_rows(comp_mat, self.layout)
        winlen0 = (state.appended - state.expired).astype(jnp.int32)

        # per-arrival expiry frontier s_j (relative to state.expired):
        # the smallest window start keeping every conjunct true after j
        p = jnp.arange(B, dtype=jnp.int32)
        q = winlen0 + p  # arrival j's relative position
        ring_cols, ring_ts = _unpack_rows(state.ring, self.layout)
        s = self._frontiers(ring_cols, ring_ts, comp_cols, comp_ts,
                            state.expired, winlen0, n_valid32, q)
        # frontiers are cumulative: a later arrival can never re-admit
        # events an earlier one expired
        s = jax.lax.associative_scan(jnp.maximum, s)
        s = jnp.clip(s, 0, q + 1)
        s_end = jnp.max(jnp.where(p < n_valid32, s, 0))
        # only E expiry lanes can emit per step: cap the frontier advance and
        # let later steps catch up (their recomputed frontiers still hold) —
        # mass expiry must never drop EXPIRED emissions
        s_end = jnp.minimum(s_end, jnp.int32(E))
        # invalid lanes take the final frontier so the trigger search scans a
        # SORTED array (trailing zeros would break the binary search)
        s_sorted = jnp.where(p < n_valid32, jnp.minimum(s, s_end), s_end)

        appended1 = state.appended + n_valid

        # ---- expiry candidates ----
        pe = jnp.arange(E, dtype=jnp.int32)
        cand_exists = pe < (appended1 - state.expired).astype(jnp.int32)
        cand_mat = _fetch_rel_packed(
            state.ring, comp_mat, state.expired, state.appended, E)
        expires = cand_exists & (pe < s_end)
        # trigger: the FIRST arrival whose frontier passes this candidate;
        # reference pops AFTER processing the arrival, so expired lanes sort
        # just after their trigger arrival
        trig = searchsorted32(s_sorted, pe + 1, side="left")
        emit_ts = jnp.broadcast_to(jnp.asarray(now, jnp.int64), (E,))

        cur_valid = p < n_valid32
        # reference pops AFTER processing the triggering arrival: expired
        # lanes sort just after their trigger (slot 3 of the position, past
        # CURRENT's 2) and before the next arrival
        keys_exp = jnp.clip(trig, 0, B) * 4 + 3
        keys_cur = p * 4 + KIND_CURRENT

        all_hi = jnp.concatenate([keys_exp, keys_cur])
        all_lo = jnp.concatenate([pe, p])
        all_mat = jnp.concatenate([cand_mat, comp_mat], axis=1)
        all_emit = jnp.concatenate([emit_ts, comp_ts])
        all_valid = jnp.concatenate([expires, cur_valid])
        all_types = jnp.concatenate([
            jnp.full((E,), EventType.EXPIRED, jnp.int8),
            jnp.full((B,), EventType.CURRENT, jnp.int8),
        ])
        chunk = _sort_chunk_packed(all_hi, all_lo, all_mat, all_emit,
                                   all_valid, all_types, self.layout,
                                   self.chunk_width)

        new_ring = _append_packed(state.ring, comp_mat, state.appended,
                                  n_valid32)
        # sum/span conjuncts have no static bound: count live rows the ring
        # wrap overwrote (ADVICE r02: count() forms are sized statically)
        expired1 = state.expired + s_end.astype(jnp.int64)
        over0 = jnp.maximum(state.appended - state.expired - C, 0)
        over1 = jnp.maximum(appended1 - expired1 - C, 0)
        new_state = state._replace(
            ring=new_ring,
            appended=appended1,
            expired=expired1,
            overflow=state.overflow + jnp.maximum(over1 - over0, 0),
            live_hwm=jnp.maximum(state.live_hwm, appended1 - expired1),
        )
        return new_state, chunk

    def contents(self, state: SlidingState, now: jax.Array):
        ring_cols, ring_ts = _unpack_rows(state.ring, self.layout)
        live = _ring_live_mask(self.C, state.expired, state.appended)
        return ring_cols, ring_ts, live
