"""The lint rule catalog (SL1xx: static plan rules).

Each rule is a function over a PlanGraph that yields Diagnostics. Rules run
inside a guard — a crashing rule is dropped (and logged at debug), never
surfaced to app creation — and every finding passes the suppression filter
(`@suppress.lint('SL101', ...)` on the element or the app) before it lands
in the report.

Severity policy: ERROR marks defects that build fine but are wrong at
runtime (silent query shadowing, dead fault wiring) or that creation would
reject anyway; WARN marks unbounded-state and config hazards; INFO marks
performance footnotes (silent numeric promotion, pad-back copies).
"""

from __future__ import annotations

import logging
import re
from typing import Callable, Iterable, Optional

from ..query_api.definition import AttributeType
from ..query_api.execution import (
    EveryStateElement,
    JoinInputStream,
    LogicalStateElement,
    NextStateElement,
    CountStateElement,
    Query,
    StateInputStream,
)
from ..query_api.expression import (
    And,
    Compare,
    CompareOp,
    Constant,
    Expression,
    Not,
    Or,
)
from .diagnostics import Diagnostic, LintReport, Severity
from .plan import ExprTyper, PlanGraph, QueryNode, _frames_for, _output_schema

log = logging.getLogger("siddhi_tpu.lint")

#: (rule_id, severity, checker, one-line description) — docs/LINT.md mirrors
#: this table
RULES: list[tuple[str, Severity, Callable, str]] = []


def rule(rule_id: str, severity: Severity, description: str):
    def deco(fn):
        RULES.append((rule_id, severity, fn, description))
        return fn
    return deco


def run_rules(plan: PlanGraph, report: LintReport) -> None:
    for rule_id, severity, fn, _desc in RULES:
        try:
            findings = fn(plan) or ()
        except Exception:
            log.debug("lint rule %s crashed; skipped", rule_id, exc_info=True)
            continue
        for element, message, anchor, loc in findings:
            if plan.suppressions.is_suppressed(rule_id, anchor):
                continue
            report.add(Diagnostic(rule_id, severity, message,
                                  element=element, loc=loc))


def _q(node: QueryNode, message: str):
    """Finding anchored at a query."""
    return (node.name, message, node.query, node.loc)


def _d(name: str, defn, message: str):
    """Finding anchored at a definition."""
    return (name, message, defn, getattr(defn, "loc", None))


# ------------------------------------------------------------- SL101 / SL102


@rule("SL101", Severity.ERROR,
      "a query consumes a stream that is neither defined nor produced")
def undefined_stream(plan: PlanGraph) -> Iterable:
    for node in plan.queries:
        for c in node.consumed:
            if c.stream_id in plan.schemas:
                continue
            if c.is_fault:
                continue  # base-stream existence is SL111's concern
            kind = "partition inner stream" if c.is_inner else "stream"
            yield _q(node, f"{kind} {c.stream_id!r} is not defined and no "
                           "query inserts into it")


@rule("SL102", Severity.WARN,
      "a defined stream is fully disconnected (no producer, consumer, "
      "@source or @sink)")
def unused_stream(plan: PlanGraph) -> Iterable:
    if not plan.queries:
        return  # definition-only apps feed everything externally
    for sid, schema in plan.schemas.items():
        if schema.kind != "stream" or schema.defn is None:
            continue
        d = schema.defn
        if sid in plan.consumers or sid in plan.producers:
            continue
        if any(a.name.lower() in ("source", "sink", "export", "import")
               for a in d.annotations or ()):
            continue
        yield _d(sid, d, f"stream {sid!r} is never consumed or produced by "
                         "any query and has no @source/@sink")


# ------------------------------------------------- SL103 / SL104 / SL105


def _filter_exprs(node: QueryNode):
    for c in node.consumed:
        h = c.single.handlers
        for f in h.filters:
            yield f, (c.single.alias or c.stream_id)
        for f in h.post_window_filters:
            yield f, (c.single.alias or c.stream_id)


def _type_check(node: QueryNode, plan: PlanGraph):
    """One typing pass per query: returns (issues, promotions)."""
    frames = _frames_for(node, plan)
    typer = ExprTyper(frames)

    for f, _ref in _filter_exprs(node):
        t = typer.type_of(f)
        if t is not None and t != AttributeType.BOOL:
            typer.issues.append(
                ("SL104", f"filter expression must be bool, got {t.value}"))

    ins = node.query.input_stream
    if isinstance(ins, JoinInputStream) and ins.on is not None:
        t = typer.type_of(ins.on)
        if t is not None and t != AttributeType.BOOL:
            typer.issues.append(
                ("SL104", f"join `on` condition must be bool, got {t.value}"))

    sel = node.query.selector
    for attr in sel.attributes:
        typer.type_of(attr.expression)
    for v in sel.group_by:
        typer.type_of(v)

    # having / order by see the select list's output columns too
    out_attrs = _output_schema(node, plan)
    post_frames = dict(frames)
    post_frames["#out"] = out_attrs
    post = ExprTyper(post_frames)
    if sel.having is not None:
        t = post.type_of(sel.having)
        if t is not None and t != AttributeType.BOOL:
            post.issues.append(
                ("SL104", f"having condition must be bool, got {t.value}"))
    for ob in sel.order_by:
        post.type_of(ob.variable)

    # delete/update ... on <cond> additionally sees the target table
    out = node.query.output_stream
    if out.on_condition is not None and out.target_id:
        tbl = plan.schemas.get(out.target_id)
        cond_frames = dict(frames)
        cond_frames[out.target_id] = tbl.attrs if tbl else None
        ct = ExprTyper(cond_frames)
        t = ct.type_of(out.on_condition)
        if t is not None and t != AttributeType.BOOL:
            ct.issues.append(
                ("SL104", f"`on` condition must be bool, got {t.value}"))
        typer.issues.extend(ct.issues)
        typer.promotions.extend(ct.promotions)

    typer.issues.extend(post.issues)
    typer.promotions.extend(post.promotions)
    return typer.issues, typer.promotions


def _typing_findings(plan: PlanGraph, want_code: str, promotions: bool = False):
    for node in plan.queries:
        issues, promos = _type_check(node, plan)
        if promotions:
            for msg in promos:
                yield _q(node, msg)
        else:
            seen = set()
            for code, msg in issues:
                if code == want_code and msg not in seen:
                    seen.add(msg)
                    yield _q(node, msg)


@rule("SL103", Severity.ERROR,
      "an expression references an attribute its input streams do not define")
def undefined_attribute(plan: PlanGraph) -> Iterable:
    yield from _typing_findings(plan, "SL103")


@rule("SL104", Severity.ERROR,
      "expression dtype mismatch (non-bool filter, string arithmetic, "
      "string ordering, bool/numeric comparison)")
def type_mismatch(plan: PlanGraph) -> Iterable:
    yield from _typing_findings(plan, "SL104")


@rule("SL105", Severity.INFO,
      "silent numeric promotion: integral and floating operands mix, the "
      "integral side loses precision on device")
def silent_promotion(plan: PlanGraph) -> Iterable:
    for node in plan.queries:
        _issues, promos = _type_check(node, plan)
        for msg in dict.fromkeys(promos):
            yield _q(node, msg)


# --------------------------------------------------- SL106 / SL107 / SL108


@rule("SL106", Severity.WARN,
      "join over a raw (unwindowed) stream retains every event forever")
def unbounded_join(plan: PlanGraph) -> Iterable:
    for node in plan.queries:
        ins = node.query.input_stream
        if not isinstance(ins, JoinInputStream):
            continue
        kinds = []
        for side in (ins.left, ins.right):
            schema = plan.schemas.get(side.stream_id)
            kinds.append(schema.kind if schema else "stream")
        for (side, label), other in zip(((ins.left, "left"),
                                         (ins.right, "right")),
                                        reversed(kinds)):
            schema = plan.schemas.get(side.stream_id)
            kind = schema.kind if schema else "stream"
            if kind in ("table", "window", "aggregation"):
                continue  # bounded by the store's own retention
            if other in ("table", "aggregation"):
                continue  # a table or an aggregation never probes this side
            if side.handlers.window is None:
                yield _q(node, f"{label} join side {side.stream_id!r} has no "
                               "window: its join buffer grows without "
                               "eviction (add #window.time/length or join a "
                               "table)")


def _has_every(state) -> bool:
    if isinstance(state, EveryStateElement):
        return True
    if isinstance(state, NextStateElement):
        return _has_every(state.state) or _has_every(state.next)
    if isinstance(state, LogicalStateElement):
        return _has_every(state.left) or _has_every(state.right)
    if isinstance(state, CountStateElement):
        return _has_every(state.element)
    return False


@rule("SL107", Severity.WARN,
      "pattern with `every` but no `within`: partial matches re-arm and "
      "accumulate unboundedly")
def every_without_within(plan: PlanGraph) -> Iterable:
    for node in plan.queries:
        ins = node.query.input_stream
        if not isinstance(ins, StateInputStream):
            continue
        if ins.within_ms is None and _has_every(ins.state):
            yield _q(node, "`every` pattern has no `within` bound: every "
                           "arrival re-arms the NFA and partial matches are "
                           "never expired (add `within <time>`)")


@rule("SL108", Severity.WARN,
      "named window defined without a window spec never evicts")
def window_without_eviction(plan: PlanGraph) -> Iterable:
    for wid, d in plan.app.window_definitions.items():
        if d.window is None:
            yield _d(wid, d, f"define window {wid!r} carries no window "
                             "specification: nothing is ever evicted")


# --------------------------------------------------- SL109 / SL110 / SL111


@rule("SL109", Severity.ERROR,
      "two queries share an @info name: the later silently shadows the "
      "earlier in runtime addressing")
def shadowed_query(plan: PlanGraph) -> Iterable:
    by_name: dict[str, list[QueryNode]] = {}
    for node in plan.queries:
        if node.explicit_name:
            by_name.setdefault(node.name, []).append(node)
    for name, nodes in by_name.items():
        for later in nodes[1:]:
            yield _q(later, f"query name {name!r} is already used by an "
                            "earlier query; callbacks and statistics "
                            "addressed by name silently bind to only one "
                            "of them")


def _const_fold(expr: Expression):
    """Fold constant boolean expressions; None = not statically known."""
    if isinstance(expr, Constant):
        if expr.type_name == "bool":
            return bool(expr.value)
        return None
    if isinstance(expr, Not):
        inner = _const_fold(expr.expression)
        return None if inner is None else not inner
    if isinstance(expr, And):
        l, r = _const_fold(expr.left), _const_fold(expr.right)
        if l is False or r is False:
            return False
        if l is True and r is True:
            return True
        return None
    if isinstance(expr, Or):
        l, r = _const_fold(expr.left), _const_fold(expr.right)
        if l is True or r is True:
            return True
        if l is False and r is False:
            return False
        return None
    if isinstance(expr, Compare):
        lc, rc = expr.left, expr.right
        if not (isinstance(lc, Constant) and isinstance(rc, Constant)):
            return None
        lv, rv = lc.value, rc.value
        if isinstance(lv, bool) != isinstance(rv, bool):
            return None
        if isinstance(lv, str) != isinstance(rv, str):
            return None
        try:
            return {
                CompareOp.EQUAL: lv == rv,
                CompareOp.NOT_EQUAL: lv != rv,
                CompareOp.GREATER_THAN: lv > rv,
                CompareOp.GREATER_THAN_EQUAL: lv >= rv,
                CompareOp.LESS_THAN: lv < rv,
                CompareOp.LESS_THAN_EQUAL: lv <= rv,
            }[expr.op]
        except TypeError:
            return None
    return None


@rule("SL110", Severity.WARN,
      "a filter folds to constant false: the query can never emit")
def dead_query(plan: PlanGraph) -> Iterable:
    for node in plan.queries:
        for f, ref in _filter_exprs(node):
            if _const_fold(f) is False:
                yield _q(node, f"filter on {ref!r} is constant false — the "
                               "query is dead (no event can ever pass)")


@rule("SL111", Severity.ERROR,
      "fault-stream wiring (`!S`) without @OnError(action='STREAM') on S")
def fault_wiring(plan: PlanGraph) -> Iterable:
    def has_fault_stream(sid: str) -> bool:
        schema = plan.schemas.get(sid)
        d = schema.defn if schema else None
        if d is None or not getattr(d, "annotations", None):
            return False
        for ann in d.annotations:
            if ann.name.lower() == "onerror":
                action = (ann.element("action") or "log")
                return str(action).lower() == "stream"
        return False

    for node in plan.queries:
        for c in node.consumed:
            if c.is_fault and not has_fault_stream(c.stream_id):
                yield _q(node, f"`from !{c.stream_id}` consumes a fault "
                               f"stream, but {c.stream_id!r} does not "
                               "declare @OnError(action='STREAM') so no "
                               "fault stream exists")
        out = node.query.output_stream
        if node.produces_fault and not has_fault_stream(node.produces):
            yield _q(node, f"`insert into !{out.target_id}` targets a fault "
                           f"stream, but {out.target_id!r} does not declare "
                           "@OnError(action='STREAM')")


# ------------------------------------------------------------------- SL112


_TIME_RE = re.compile(r"^\s*(\d+(?:\.\d+)?)\s*"
                      r"(ms|milli\w*|sec\w*|min\w*|hour\w*|day\w*)?\s*$",
                      re.IGNORECASE)
_TIME_MS = {"ms": 1, "milli": 1, "sec": 1000, "min": 60_000,
            "hour": 3_600_000, "day": 86_400_000}


def _ann_time_ms(text: str) -> Optional[float]:
    m = _TIME_RE.match(str(text))
    if not m:
        return None
    value = float(m.group(1))
    unit = (m.group(2) or "ms").lower()
    for prefix, ms in _TIME_MS.items():
        if unit.startswith(prefix):
            return value * ms
    return value


@rule("SL112", Severity.ERROR,
      "nonsensical @Async/@breaker configuration (inverted watermarks, "
      "threshold < 1, max.staged < buffer.size, unknown overflow policy)")
def bad_backpressure_config(plan: PlanGraph) -> Iterable:
    for sid, schema in plan.schemas.items():
        d = schema.defn
        if d is None or not getattr(d, "annotations", None):
            continue
        ann = next((a for a in d.annotations
                    if a.name.lower() == "async"), None)
        if ann is None:
            continue

        def num(key):
            v = ann.element(key)
            try:
                return float(v) if v is not None else None
            except (TypeError, ValueError):
                return None

        buf = num("buffer.size")
        if buf is not None and buf <= 0:
            yield _d(sid, d, f"@Async on {sid!r}: buffer.size must be "
                             "positive")
        staged = num("max.staged")
        if buf is not None and staged is not None and staged < buf:
            yield _d(sid, d, f"@Async on {sid!r}: max.staged ({staged:g}) "
                             f"must be >= buffer.size ({buf:g})")
        pol = ann.element("overflow.policy")
        if pol is not None and str(pol).lower() not in (
                "block", "drop.new", "drop.old", "fault"):
            yield _d(sid, d, f"@Async on {sid!r}: unknown overflow.policy "
                             f"{pol!r} (block | drop.new | drop.old | fault)")
        hw = num("high.watermark")
        lw = num("low.watermark")
        hw_v = 0.8 if hw is None else hw
        lw_v = 0.2 if lw is None else lw
        if (hw is not None or lw is not None) and not (
                0.0 <= lw_v < hw_v <= 1.0):
            yield _d(sid, d, f"@Async on {sid!r}: watermarks must satisfy "
                             f"0 <= low.watermark ({lw_v:g}) < "
                             f"high.watermark ({hw_v:g}) <= 1")

    for node in plan.queries:
        ann = next((a for a in node.query.annotations
                    if a.name.lower() == "breaker"), None)
        if ann is None:
            continue
        thr = ann.element("threshold")
        if thr is not None:
            try:
                if int(str(thr)) < 1:
                    yield _q(node, f"@breaker threshold ({thr}) must be "
                                   ">= 1 — a breaker that trips on zero "
                                   "failures never closes")
            except ValueError:
                yield _q(node, f"@breaker threshold {thr!r} is not an "
                               "integer")
        for key in ("window", "cooldown"):
            v = ann.element(key)
            if v is None:
                continue
            ms = _ann_time_ms(v)
            if ms is None:
                yield _q(node, f"@breaker {key} {v!r} is not a time "
                               "literal (e.g. '30 sec')")
            elif ms <= 0:
                yield _q(node, f"@breaker {key} must be positive, got {v!r}")


# ------------------------------------------------------------------- SL113


#: window names whose device implementation consumes variable-lane batches
#: directly (ops/windows.py shape_polymorphic=True); every other window is
#: shape-baked: bucketed batches pad back to full capacity before the step
_SHAPE_POLYMORPHIC_WINDOWS = {"time"}


@rule("SL113", Severity.WARN,
      "shape buckets are enabled but the query's step is shape-baked: "
      "every partial batch pads back to full capacity")
def shape_bucket_padback(plan: PlanGraph) -> Iterable:
    from ..core import dtypes
    if not dtypes.config.shape_buckets:
        return
    for node in plan.queries:
        for c in node.consumed:
            if c.role != "single":
                continue  # joins/patterns are shape-baked by design
            w = c.single.handlers.window
            if w is None:
                continue  # pass-through is shape-polymorphic
            if w.name in _SHAPE_POLYMORPHIC_WINDOWS:
                continue
            if node.partition is not None \
                    and partition_engine(node.partition, plan) is None:
                continue  # the keyed step reads its width from the batch
            if w.name == "batch" and not w.parameters:
                continue  # paramless batch lowers to pass-through
            yield _q(node, f"#window.{w.name} is shape-baked while shape "
                           "buckets are on: small batches pad back to the "
                           "full batch capacity each step (copies, no "
                           "per-bucket kernels); use #window.time for "
                           "shape-polymorphic steps or set "
                           "SIDDHI_SHAPE_BUCKETS=0")


# ------------------------------------------------------------------- SL114


@rule("SL114", Severity.INFO,
      "co-resident queries on one stream can share a compiled step "
      "(multi-query optimizer: @app:optimize / SIDDHI_OPTIMIZE=1)")
def shareable_work(plan: PlanGraph) -> Iterable:
    from .optimizer import analyze_sharing
    report = analyze_sharing(plan)
    verb = "fuses" if report.enabled else "would fuse (optimizer off)"
    for g in report.groups:
        anchor = g.nodes[0]
        yield _q(anchor,
                 f"stream {g.stream_id!r}: optimizer {verb} "
                 f"{len(g.members)} queries ({', '.join(g.members)}) into "
                 f"one compiled step — {g.shared_subexpressions} shared "
                 f"subexpression(s), {g.pushdowns} pushable predicate(s), "
                 f"{g.pane_candidates} span-correlated window(s); saves "
                 f"{g.steps_saved} step dispatch(es)/compile(s) per batch")
    for node, reason in report.declined_nodes:
        yield _q(node, "optimizer declines to fuse this query even though "
                       f"its stream hosts shareable work: {reason}")


# ------------------------------------------------------------------- SL116


_EXTERNAL_TIME_WINDOWS = {"externaltime", "externaltimebatch"}


@rule("SL116", Severity.ERROR,
      "externalTime window fed from an @Async(workers>1) multi-producer "
      "stream with no @app:eventTime lateness declared: racing producers "
      "make the max-seen watermark nondeterministic")
def racing_external_time(plan: PlanGraph) -> Iterable:
    # N ingress workers race each other into the columnar ring, so the order
    # the window sees — and therefore every max-seen watermark advance and
    # pane close — varies run to run. @app:eventTime(allowed.lateness=...)
    # is the fix: the ingress gate re-sorts arrivals by event time (bounded
    # by the lateness budget) before the device ever sees them.
    et_ann = plan.app.annotation("app:eventTime")
    if et_ann is not None and et_ann.element("allowed.lateness"):
        return

    def workers(sid: str) -> int:
        schema = plan.schemas.get(sid)
        d = schema.defn if schema else None
        if d is None or not getattr(d, "annotations", None):
            return 0
        ann = next((a for a in d.annotations
                    if a.name.lower() == "async"), None)
        if ann is None:
            return 0
        try:
            return int(ann.element("workers") or 0)
        except (TypeError, ValueError):
            return 0

    for node in plan.queries:
        for c in node.consumed:
            w = c.single.handlers.window
            if w is None or w.name.lower() not in _EXTERNAL_TIME_WINDOWS:
                continue
            n = workers(c.stream_id)
            if n > 1:
                fix = ("declare @app:eventTime(timestamp='<attr>', "
                       "allowed.lateness='...') so arrivals sort before "
                       "the window" if et_ann is None else
                       "add allowed.lateness to @app:eventTime")
                yield _q(node, f"#window.{w.name} consumes "
                               f"{c.stream_id!r} which @Async(workers={n}) "
                               "fills from racing producers: the max-seen "
                               "event-time watermark (and every pane close) "
                               f"becomes nondeterministic — {fix}")


# ------------------------------------------------------------------- SL119
# which positions of a pattern are matched by key: the key-and-threshold
# plan (core/pattern_range.py) decided by core/pattern_runtime's
# threshold_plan, the one the runtime builds from.


@rule("SL119", Severity.INFO,
      "a pattern is matched by key and a threshold per partial match, "
      "not through the dense [B, P] mask")
def pattern_on_threshold_plan(plan: PlanGraph) -> Iterable:
    from ..core import dtypes
    from ..core.pattern_runtime import threshold_plan_of
    from ..extension.registry import GLOBAL

    def attrs(sid):
        schema = plan.schemas.get(sid)
        if schema is None or schema.kind != "stream":
            return None
        return schema.attrs

    for node in plan.queries:
        if not isinstance(node.query.input_stream, StateInputStream):
            continue
        if threshold_plan_of(node.query, attrs, GLOBAL) is None:
            continue
        stated = dtypes.stated_capacity(node.query.annotations)
        yield _q(node, "its second position (a key `arriving.k == e1.k`, "
                       "one `arriving.attr <op> <expression of e1>`, "
                       "conjuncts of the arrival alone) is matched by key "
                       "and each partial match's threshold: a row of "
                       "partial matches a key (@capacity(keys="
                       f"{stated.keys!r}), pending={stated.pending!r}); a "
                       "partial match whose row is full is dropped and "
                       "counted — see docs/PARITY.md")


# ------------------------------------------------------------------- SL120
# which engine a table with a primary key takes: the device index
# (core/table_index.py: one program a write batch, a probe by the key one
# lookup a lane) or the columnar [B, C] path. The decision is
# core/table_index.index_refusal's, the one the runtime builds from.


@rule("SL120", Severity.INFO,
      "a table with a @PrimaryKey is written and probed through a device "
      "index of its key, or says why it stays on the [B, C] path")
def table_engine(plan: PlanGraph) -> Iterable:
    from ..core import dtypes
    from ..core.table_index import index_refusal
    for tid, td in plan.app.table_definitions.items():
        if td.annotation("PrimaryKey") is None:
            continue
        reason = index_refusal(td, plan.app)
        if reason is None:
            rows = dtypes.stated_capacity(td.annotations).rows \
                or dtypes.config.default_table_capacity
            yield _d(tid, td,
                     f"table {tid!r} is written and probed through a device "
                     f"index of its key: {rows} rows (@capacity(rows=...)); "
                     "within a batch `update or insert` keeps the last "
                     "event of a key, `insert into` the first; a key with "
                     "no free row is dropped and counted in "
                     "statistics_report()['tables'] — see docs/PARITY.md")
        else:
            yield _d(tid, td,
                     f"table {tid!r} stays on the [B, C] path (a mask of "
                     f"batch x rows a write) because {reason}; the device "
                     "index takes a single int, long or string @PrimaryKey "
                     "written by `insert into` and `update or insert ... on "
                     "T.key == key` whose `set` reads no table attribute")


# ----------------------------------------------------------- SL117 / SL118
# which engine a stateful partition takes: the keyed step (one dispatch a
# batch, the keys an axis of one state) or the host loop (one dispatch a key
# a batch). The decision is core/keyed_partition.keyed_step_refusal's, the
# one the runtime builds from.


def partition_engine(partition, plan: PlanGraph) -> Optional[str]:
    """None: the keyed step; else why the partition stays on the host
    loop."""
    from ..core.keyed_partition import keyed_step_refusal
    from ..extension.registry import GLOBAL

    def attribute_types(sid):
        schema = plan.schemas.get(sid)
        if schema is None or schema.kind != "stream" or schema.attrs is None:
            return None
        return schema.attrs

    return keyed_step_refusal(partition, attribute_types, GLOBAL)


def _partition_is_stateless(partition) -> bool:
    """No window, aggregate, group or rate limit in any inner query: one
    full-width pass, no per-key state (core/partition.py `route`)."""
    from ..core.keyed_partition import _walk
    from ..extension.registry import GLOBAL, ExtensionKind
    from ..query_api.execution import SingleInputStream
    from ..query_api.expression import AttributeFunction
    for q in partition.queries:
        ins = q.input_stream
        if not isinstance(ins, SingleInputStream) \
                or ins.handlers.window is not None \
                or q.selector.group_by or q.output_rate is not None:
            return False
        for a in q.selector.attributes:
            if any(isinstance(n, AttributeFunction) and GLOBAL.lookup(
                    ExtensionKind.AGGREGATOR, n.namespace, n.name)
                    is not None for n in _walk(a.expression)):
                return False
    return True


def _stateful_partitions(plan: PlanGraph):
    """(name as the runtime has it, partition, host-loop reason or None)."""
    for i, p in enumerate(plan.app.partitions):
        if not _partition_is_stateless(p):
            yield f"partition{i + 1}", p, partition_engine(p, plan)


@rule("SL117", Severity.WARN,
      "a stateful partition runs on the host loop: one dispatch of the "
      "inner queries' steps per distinct key per batch")
def partition_on_host_loop(plan: PlanGraph) -> Iterable:
    for name, p, reason in _stateful_partitions(plan):
        if reason is not None:
            yield (name, f"{name} runs on the host loop (a state per key, "
                         "one dispatch of every inner step per distinct "
                         f"key per batch) because {reason}; the keyed step "
                         "(one dispatch a batch) takes a partition of one "
                         "stream by one int, long, string or bool attribute "
                         "around one `from S#window.length(L) select ...` "
                         "with sum/count/avg/min/max over the window — see "
                         "docs/PARITY.md", p, p.loc)


@rule("SL118", Severity.INFO,
      "a stateful partition runs on the keyed step: the keys are an axis of "
      "one state, sized by @capacity(keys=...)")
def partition_on_keyed_step(plan: PlanGraph) -> Iterable:
    from ..core import dtypes
    for name, p, reason in _stateful_partitions(plan):
        if reason is None:
            stated = dtypes.stated_capacity(p.annotations).keys
            held = (f"@capacity(keys='{stated}')" if stated is not None else
                    "no @capacity(keys=...): the runtime's "
                    "partition_capacity, "
                    f"{dtypes.config.default_partition_capacity} by default")
            yield (name, f"{name} runs on the keyed step: one dispatch a "
                         f"batch, state for the keys it states ({held}); "
                         "events of keys beyond that are dropped and counted "
                         "in statistics_report()['partitions']", p, p.loc)


# ------------------------------------------------------------------- SL5xx
# capacity certification: the static cost model (analysis/cost.py) priced
# against the configured budget (@app:budget / SIDDHI_STATE_BUDGET).
# docs/COST.md documents the per-operator formulas; tools/cost_calibrate.py
# holds predictions within 2x of live telemetry.


def _query_by_index(plan: PlanGraph, index) -> Optional[QueryNode]:
    return next((n for n in plan.queries if n.index == index), None)


def _cost_anchor(plan: PlanGraph, rep) -> Optional[QueryNode]:
    """Anchor app-level cost findings at the dominant element's query when
    it has one, else the first query (definitions lack a natural anchor)."""
    if rep.dominant is not None and rep.dominant.node_index is not None:
        node = _query_by_index(plan, rep.dominant.node_index)
        if node is not None:
            return node
    return plan.queries[0] if plan.queries else None


@rule("SL501", Severity.ERROR,
      "predicted device state / compile ladder exceeds the configured "
      "budget (@app:budget / SIDDHI_STATE_BUDGET / SIDDHI_COMPILE_BUDGET)")
def over_budget(plan: PlanGraph) -> Iterable:
    from .cost import app_budget, cost_for_plan, format_size
    budget = app_budget(plan.app)
    if budget is None:
        return
    rep = cost_for_plan(plan)
    anchor = _cost_anchor(plan, rep)
    if anchor is None:
        return
    if budget.state_bytes is not None and rep.state_bytes > budget.state_bytes:
        dom = ""
        if rep.dominant is not None:
            dom = (f" — dominant element {rep.dominant.element!r} holds "
                   f"{format_size(rep.dominant.state_bytes)}")
        yield _q(anchor,
                 f"predicted device state {format_size(rep.state_bytes)} "
                 f"exceeds the configured budget "
                 f"{format_size(budget.state_bytes)} "
                 f"({budget.source}){dom}; shrink window/table/group "
                 "capacities or raise the budget (admission control: "
                 "creation refuses or queues this app)")
    if budget.compiles is not None and rep.compile_ladder > budget.compiles:
        yield _q(anchor,
                 f"predicted compile ladder ({rep.compile_ladder} "
                 f"executables) exceeds the configured compile budget "
                 f"({budget.compiles}, {budget.source}); fuse queries "
                 "(@app:optimize) or disable shape buckets for this app")


@rule("SL502", Severity.ERROR,
      "statically unbounded state growth while a state budget is "
      "configured: the budget cannot be certified")
def unbounded_state_growth(plan: PlanGraph) -> Iterable:
    from .cost import app_budget
    budget = app_budget(plan.app)
    if budget is None or budget.state_bytes is None:
        return
    for node in plan.queries:
        ins = node.query.input_stream
        if isinstance(ins, JoinInputStream):
            for side in (ins.left, ins.right):
                schema = plan.schemas.get(side.stream_id)
                kind = schema.kind if schema is not None else None
                if kind in ("table", "window", "aggregation"):
                    continue  # store-backed sides have their own bounds
                if side.handlers.window is None:
                    yield _q(node,
                             f"join side {side.stream_id!r} has no "
                             "retention window: its state demand is "
                             "statically unbounded, so the configured "
                             "state budget cannot be certified — add "
                             "#window.time/#window.length to the side")
        frames = _frames_for(node, plan)
        typer = ExprTyper(frames)
        for g in node.query.selector.group_by:
            if typer.type_of(g) == AttributeType.STRING:
                yield _q(node,
                         "group by over a raw string key: the host intern "
                         "table grows with key cardinality without bound, "
                         "so the configured state budget cannot be "
                         "certified — bound the key domain or group by an "
                         "integer key")
    for sid, schema in plan.schemas.items():
        if schema.kind != "window" or schema.defn is None:
            continue
        if getattr(schema.defn, "window", None) is None:
            yield _d(sid, schema.defn,
                     f"named window {sid!r} declares no retention spec: "
                     "its contents contract is unbounded in the reference "
                     "semantics, so the configured state budget cannot be "
                     "certified — declare an explicit window spec")


@rule("SL503", Severity.WARN,
      "compile-ladder explosion: predicted executable count exceeds the "
      "threshold (budget compiles / SIDDHI_COMPILE_LADDER_WARN, default 64)")
def compile_ladder_explosion(plan: PlanGraph) -> Iterable:
    import os
    from .cost import app_budget, cost_for_plan
    budget = app_budget(plan.app)
    if budget is not None and budget.compiles is not None:
        threshold = budget.compiles
    else:
        try:
            threshold = int(
                os.environ.get("SIDDHI_COMPILE_LADDER_WARN", "") or 64)
        except ValueError:
            threshold = 64
    rep = cost_for_plan(plan)
    if rep.compile_ladder <= threshold or not plan.queries:
        return
    yield _q(plan.queries[0],
             f"predicted compile ladder: {rep.compile_ladder} executables "
             f"(> {threshold}) across shape buckets x queries x steps — "
             "expect a long warmup and a large executable cache; fuse "
             "co-resident queries (@app:optimize), reduce query count, or "
             "set SIDDHI_SHAPE_BUCKETS=0")


@rule("SL505", Severity.INFO,
      "cost-dominant element: one element holds >50% of the app's "
      "predicted device state")
def cost_dominant_element(plan: PlanGraph) -> Iterable:
    import os
    from .cost import cost_for_plan, format_size, parse_size
    try:
        floor = parse_size(
            os.environ.get("SIDDHI_COST_NOTE_MIN", "") or "64MiB")
    except ValueError:
        floor = 64 << 20
    rep = cost_for_plan(plan)
    if rep.state_bytes < floor or rep.dominant is None:
        return
    e = rep.dominant
    msg = (f"element {e.element!r} holds {format_size(e.state_bytes)} of "
           f"{format_size(rep.state_bytes)} predicted device state "
           f"({rep.dominant_share:.0%}) — the first target for capacity "
           "tuning (docs/COST.md)")
    if e.node_index is not None:
        node = _query_by_index(plan, e.node_index)
        if node is not None:
            yield _q(node, msg)
            return
    schema = plan.schemas.get(e.element)
    if schema is not None and schema.defn is not None:
        yield _d(e.element, schema.defn, msg)


def _superstep_ineligibility(plan: PlanGraph):
    """First STATIC reason the superstep scan would decline this plan, as
    (reason, anchor-node-or-None) — or None when nothing statically rules
    it out. A lightweight mirror of core/superstep.py's runtime decline
    taxonomy: only the facts visible in the AST/plan are checked (the
    runtime additionally declines on breakers, tables, sinks, callbacks
    registered after creation, ...)."""
    import os
    app = plan.app
    try:
        env_workers = int(os.environ.get("SIDDHI_INGRESS_WORKERS", "0") or 0)
    except ValueError:
        env_workers = 0
    async_sids = []
    for sid, schema in plan.schemas.items():
        d = schema.defn
        if schema.kind != "stream" or d is None or not d.annotations:
            continue
        ann = d.annotation("async")
        if ann is None:
            continue
        try:
            w = ann.element("workers")
            workers = int(w) if w else env_workers
        except ValueError:
            workers = env_workers
        if workers > 0:
            async_sids.append(sid)
    if not async_sids:
        return ("no @Async(workers=) stream: the ingress pipeline — and "
                "with it the superstep feeder — never engages", None)
    if app is not None and app.annotation("app:playback") is not None:
        return ("@app:playback drives virtual time per delivered batch, "
                "but a superstep samples `now` once per K batches", None)
    for sid in async_sids:
        schema = plan.schemas[sid]
        if any(a.type == AttributeType.OBJECT
               for a in schema.defn.attributes):
            return (f"stream {sid!r} carries OBJECT columns, which stay "
                    "host-side", None)
        for node in plan.queries:
            if all(c.stream_id != sid for c in node.consumed):
                continue
            if node.partition is not None:
                return ("a partitioned query consumes the @Async stream "
                        f"{sid!r}: per-key instances dispatch host-side",
                        node)
            if isinstance(node.query.input_stream, StateInputStream):
                return ("a pattern/sequence query consumes the @Async "
                        f"stream {sid!r}: NFA steps are not scannable "
                        "receivers", node)
    return None


@rule("SL506", Severity.INFO,
      "superstep requested (@app:superstep k>1) but the plan is statically "
      "ineligible: the ingress feeder will fall back to per-batch dispatch")
def superstep_ineligible(plan: PlanGraph) -> Iterable:
    from .cost import superstep_k
    k = superstep_k(plan.app)
    if k <= 1 or not plan.queries:
        return
    found = _superstep_ineligibility(plan)
    if found is None:
        return
    reason, node = found
    anchor = node if node is not None else plan.queries[0]
    yield _q(anchor,
             f"@app:superstep(k={k}) cannot engage: {reason} — the "
             "ingress feeder falls back to per-batch (K=1) dispatch at "
             "runtime, loudly, with the reason in stats_snapshot()"
             "['superstep_decline'] (core/superstep.py decline taxonomy, "
             "docs/PERFORMANCE.md)")


@rule("SL601", Severity.ERROR,
      "shard-ineligible element under @app:shards: a global operator "
      "(count window, unkeyed aggregate, pattern, named window, trigger, "
      "non-key join) would be silently wrong when sharded")
def shard_ineligible(plan: PlanGraph) -> Iterable:
    from .sharding import shard_config, shard_violations
    cfg = shard_config(plan.app)
    if cfg is None:
        return
    for v in shard_violations(plan, cfg.key):
        msg = (f"not shard-eligible under partition key {cfg.key!r}: "
               f"{v.reason} — the shard plane will refuse this app "
               "(docs/SHARDING.md)")
        if v.node is not None:
            yield _q(v.node, msg)
        else:
            yield _d(v.element, v.defn, msg)


@rule("SL602", Severity.WARN,
      "skewed shard routing: a filter pins the partition key to one "
      "literal, so every matching row hashes to a single shard")
def skewed_shard_key(plan: PlanGraph) -> Iterable:
    from ..query_api.expression import Variable
    from .sharding import _conjuncts, shard_config
    cfg = shard_config(plan.app)
    if cfg is None:
        return
    for node in plan.queries:
        for c in node.consumed:
            chain = c.single.handlers
            for f in tuple(chain.filters) + tuple(chain.post_window_filters):
                for conj in _conjuncts(f):
                    if not (isinstance(conj, Compare)
                            and conj.op is CompareOp.EQUAL):
                        continue
                    sides = (conj.left, conj.right)
                    var = next((s for s in sides
                                if isinstance(s, Variable)
                                and s.attribute == cfg.key), None)
                    lit = next((s for s in sides
                                if isinstance(s, Constant)), None)
                    if var is None or lit is None:
                        continue
                    yield _q(node,
                             f"filter pins partition key {cfg.key!r} to "
                             f"literal {lit.value!r}: every matching row "
                             f"hashes to ONE of the {cfg.n} shards, so "
                             "this query's traffic cannot scale past one "
                             "replica — shard by a higher-cardinality "
                             "key, or drop @app:shards for this app "
                             "(docs/SHARDING.md)")


def check_query(query: Query) -> None:
    """Hook for future per-query API use; kept minimal."""
    _ = query
