"""Static capacity cost model (the SL5xx certification substrate).

Walks the plan graph (analysis/plan.py) and predicts, per element and per
app, WITHOUT building a runtime or allocating any device state:

- **state bytes** — the device-resident footprint each element's
  ``init_state()`` would allocate: window ring packs (ops/windows.py), join
  stores + hash multimaps (core/join_runtime.py), NFA pending tables
  (core/pattern_runtime.py), group-by/aggregation tables, rate-limiter
  rings. The prediction is byte-exact where the schema is closed: the model
  constructs the SAME operator objects the runtime would (window factories,
  CompiledSelector, rate limiters — all allocation-free constructors) and
  sizes their state under ``jax.eval_shape``, so formula drift is
  structurally impossible.
- **compile-ladder size** — executables XLA would compile across shape
  buckets x queries x steps (join directions, pattern per-stream steps +
  heartbeat), respecting SharedStepGroup fusion (analysis/optimizer.py)
  when the multi-query optimizer is enabled.
- **dispatch class** — whether the step is dispatched once per batch
  (``device``) or amortizes its dispatch over a K-batch superstep scan
  (``superstep``, core/superstep.py: dispatches-per-event divided by K).

Enforcement rides on top: `app_budget` reads ``@app:budget(state=,
compiles=)`` / ``SIDDHI_STATE_BUDGET`` / ``SIDDHI_COMPILE_BUDGET`` and
`SiddhiManager.create_siddhi_app_runtime` refuses (or, with
``SIDDHI_BUDGET_MODE=queue``, defers) over-budget apps before any device
state exists. `tools/cost_calibrate.py` holds predictions within a 2x band
of live telemetry. Rules SL501-SL505 (analysis/rules.py) surface the model
through lint; docs/COST.md documents the formulas.

The model is deliberately conservative about what it cannot see: open
schemas (stream functions, untypeable columns) and host-side structures
(record-table stores, event-time reorder buffers) degrade to notes with
``exact=False`` instead of guesses, so the budget gate under-reports
rather than refusing working apps (the zero-FP sweep holds the line).
"""

from __future__ import annotations

import math
import os
import re
from dataclasses import dataclass, field
from typing import Optional, Union

from ..query_api import SiddhiApp
from ..query_api.definition import AttributeType
from ..query_api.execution import (
    JoinInputStream,
    OutputEventType,
    OutputRateType,
    StateInputStream,
)
from .plan import ExprTyper, PlanGraph, QueryNode, _frames_for, build_plan

__all__ = [
    "Budget", "CostReport", "ElementCost", "app_budget", "compute_cost",
    "cost_for_plan", "format_size", "measure_runtime_state_bytes",
    "parse_size", "price_splice", "superstep_k",
]

_SIZE_RE = re.compile(
    r"^\s*(\d+(?:\.\d+)?)\s*(b|kb|kib|mb|mib|gb|gib|tb|tib)?\s*$", re.I)
_SIZE_UNITS = {
    None: 1, "b": 1,
    "kb": 1024, "kib": 1024,
    "mb": 1024 ** 2, "mib": 1024 ** 2,
    "gb": 1024 ** 3, "gib": 1024 ** 3,
    "tb": 1024 ** 4, "tib": 1024 ** 4,
}


def parse_size(text: Union[str, int]) -> int:
    """'512MB' / '1.5GiB' / '65536' -> bytes (power-of-two units)."""
    if isinstance(text, int):
        return text
    m = _SIZE_RE.match(str(text))
    if not m:
        raise ValueError(f"unparseable size {text!r} (try '512MB', '2GiB')")
    val, unit = m.groups()
    return int(float(val) * _SIZE_UNITS[unit.lower() if unit else None])


def format_size(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"  # pragma: no cover


@dataclass
class Budget:
    """Resolved capacity budget for one app (annotation and/or env)."""

    state_bytes: Optional[int] = None
    compiles: Optional[int] = None
    #: "error" refuses over-budget apps at creation; "queue" defers them to
    #: SiddhiManager.pending_apps for later admission
    mode: str = "error"
    source: str = "env"

    def to_dict(self) -> dict:
        return {"state_bytes": self.state_bytes, "compiles": self.compiles,
                "mode": self.mode, "source": self.source}


def app_budget(app: Optional[SiddhiApp]) -> Optional[Budget]:
    """``@app:budget(state='512MB', compiles='64')`` merged over the
    ``SIDDHI_STATE_BUDGET`` / ``SIDDHI_COMPILE_BUDGET`` env (annotation
    wins per field). Returns None when no budget is configured anywhere."""
    state = compiles = None
    sources = []
    env_state = os.environ.get("SIDDHI_STATE_BUDGET", "").strip()
    env_compiles = os.environ.get("SIDDHI_COMPILE_BUDGET", "").strip()
    if env_state:
        state = parse_size(env_state)
        sources.append("env")
    if env_compiles:
        compiles = int(env_compiles)
        if "env" not in sources:
            sources.append("env")
    ann = app.annotation("app:budget") if app is not None else None
    if ann is not None:
        s = ann.element("state")
        c = ann.element("compiles")
        if s:
            state = parse_size(s)
        if c:
            compiles = int(c)
        sources.insert(0, "annotation")
    if state is None and compiles is None:
        return None
    mode = os.environ.get("SIDDHI_BUDGET_MODE", "error").strip().lower()
    if mode not in ("error", "queue"):
        mode = "error"
    return Budget(state_bytes=state, compiles=compiles, mode=mode,
                  source="+".join(sources) or "env")


@dataclass
class ElementCost:
    """Predicted footprint of ONE runtime element (query or definition)."""

    element: str
    kind: str  # query | join | pattern | window | table | aggregation
    state_bytes: int = 0
    compiles: int = 0
    dispatch: str = "device"  # device | superstep
    #: byte-exact (closed schema, operator-mirrored) vs degraded estimate
    exact: bool = True
    notes: list = field(default_factory=list)
    #: plan node index for lint anchoring (queries only)
    node_index: Optional[int] = None
    #: mirrors QueryRuntime._bucket_ok (fusion-group ladder math)
    bucket_ok: bool = False

    def to_dict(self) -> dict:
        return {"element": self.element, "kind": self.kind,
                "state_bytes": self.state_bytes, "compiles": self.compiles,
                "dispatch": self.dispatch, "exact": self.exact,
                "notes": list(self.notes)}


@dataclass
class CostReport:
    """Whole-app prediction: per-element costs + the admission totals."""

    app_name: str
    state_bytes: int = 0
    compile_ladder: int = 0
    elements: list = field(default_factory=list)
    dominant: Optional[ElementCost] = None
    budget: Optional[Budget] = None
    exact: bool = True
    notes: list = field(default_factory=list)
    #: fused-group ladder summary when the optimizer is enabled:
    #: [{"stream": sid, "members": [...], "compiles": rungs}]
    fusion: list = field(default_factory=list)
    #: resolved @app:superstep(k=) / SIDDHI_SUPERSTEP_K depth (1 = per-batch)
    superstep_k: int = 1

    @property
    def dominant_share(self) -> float:
        if self.dominant is None or self.state_bytes <= 0:
            return 0.0
        return self.dominant.state_bytes / self.state_bytes

    def to_dict(self) -> dict:
        return {
            "app": self.app_name,
            "predicted_state_bytes": self.state_bytes,
            "predicted_compiles": self.compile_ladder,
            "exact": self.exact,
            "dominant": (None if self.dominant is None else {
                "element": self.dominant.element,
                "state_bytes": self.dominant.state_bytes,
                "share": round(self.dominant_share, 4)}),
            "budget": None if self.budget is None else self.budget.to_dict(),
            "elements": [e.to_dict() for e in self.elements],
            "fusion": list(self.fusion),
            "superstep_k": self.superstep_k,
            "notes": list(self.notes),
        }


# --------------------------------------------------------------------------
# sizing primitives
# --------------------------------------------------------------------------


def _tree_bytes(tree) -> int:
    """Bytes across a pytree of arrays / ShapeDtypeStructs."""
    import jax
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(math.prod(shape)) * np.dtype(dtype).itemsize
    return total


def _eval_state_bytes(fn) -> int:
    """Size ``fn()``'s pytree WITHOUT allocating: abstract evaluation only.

    Every operator ``init_state`` in this tree is pure jnp.zeros/full
    construction, so eval_shape sees the exact arrays a real call returns.
    """
    import jax
    return _tree_bytes(jax.eval_shape(fn))


def _itemsize(t: AttributeType) -> int:
    import numpy as np
    from ..core import dtypes
    return np.dtype(dtypes.device_dtype(t)).itemsize


def superstep_k(app: Optional[SiddhiApp]) -> int:
    """Resolved superstep depth for an app: ``@app:superstep(k=)`` with the
    ``SIDDHI_SUPERSTEP_K`` env overriding (same precedence the runtime
    applies in core/app_runtime.py). 1 = per-batch dispatch."""
    k = 1
    ann = app.annotation("app:superstep") if app is not None else None
    if ann is not None:
        v = ann.element("k") or ann.element()
        try:
            k = int(v) if v else 1
        except ValueError:
            k = 1
    env_k = os.environ.get("SIDDHI_SUPERSTEP_K", "").strip()
    if env_k:
        try:
            k = int(env_k)
        except ValueError:
            pass
    return max(1, k)


def _closed(attrs: Optional[dict]) -> Optional[dict]:
    """A frame usable for byte-exact construction: present, no untypeable
    columns, no host-only OBJECT columns left after filtering."""
    if attrs is None or any(t is None for t in attrs.values()):
        return None
    return {n: t for n, t in attrs.items() if t != AttributeType.OBJECT}


def _ladder_rungs(batch_cap: int) -> int:
    from ..core import dtypes
    return len(dtypes.bucket_ladder(batch_cap))


def _make_window(handlers_window, layout, batch_cap: int, expired_on: bool,
                 registry, annotations=()):
    """The runtime's own window construction (allocation-free), so that a
    capacity the app states (`@capacity(window=, expire=)`) is priced."""
    from ..ops.window_factories import make_window
    return make_window(handlers_window, layout, batch_cap, expired_on,
                       registry, annotations=annotations)


# --------------------------------------------------------------------------
# per-element models
# --------------------------------------------------------------------------


def _single_query_cost(node: QueryNode, plan: PlanGraph, registry,
                       batch_cap: int, group_cap: int,
                       name: str) -> ElementCost:
    from ..core import dtypes
    from ..core.query_runtime import _selects_aggregates
    from ..ops.expr_compile import TypeResolver
    from ..ops.ratelimit import make_rate_limiter
    from ..ops.selector import CompiledSelector
    from ..ops.windows import (LengthBatchWindow, PassThroughWindow,
                               SlidingWindow, TimeBatchWindow, WindowOp,
                               make_layout)

    ec = ElementCost(name, "query", node_index=node.index)
    c = node.consumed[0]
    frames = _frames_for(node, plan)
    frame_ref = c.single.alias or c.stream_id
    attrs = _closed(frames.get(frame_ref))
    if attrs is None:
        ec.exact = False
        ec.notes.append("open schema (stream functions or untypeable "
                        "columns): state not statically derivable")
        ec.compiles = 1
        return ec

    query = node.query
    layout = make_layout(attrs)
    expired_on = query.output_stream.event_type != OutputEventType.CURRENT
    selects_aggs = _selects_aggregates(query.selector, registry)
    snapshot_full = (query.output_rate is not None
                     and query.output_rate.type == OutputRateType.SNAPSHOT
                     and not selects_aggs)
    if snapshot_full:
        expired_on = True
    window = _make_window(c.single.handlers.window, layout, batch_cap,
                          expired_on, registry, query.annotations)
    is_sliding = c.single.handlers.window is not None and \
        type(window).__name__ in ("SlidingWindow", "ExpressionWindow",
                                  "GeneralExpressionWindow")

    resolver = TypeResolver(
        {r: f for r, f in frames.items() if _closed(f) is not None},
        frame_ref)
    select_all = list(attrs.items())
    selector = CompiledSelector(
        query.selector, resolver, registry, group_cap, frame_ref,
        select_all_attrs=select_all, sliding_window=is_sliding)

    out_layout = {n: dtypes.device_dtype(t)
                  for n, t in selector.out_types.items()
                  if t != AttributeType.OBJECT}
    fifo = isinstance(window,
                      (SlidingWindow, LengthBatchWindow, TimeBatchWindow))
    findable = type(window).contents is not WindowOp.contents \
        and not isinstance(window, PassThroughWindow)
    limiter = make_rate_limiter(
        query.output_rate, out_layout, window.chunk_width,
        grouped=bool(query.selector.group_by),
        group_capacity=group_cap,
        fifo_window=fifo and snapshot_full,
        has_aggregates=selects_aggs,
        window_capacity=getattr(window, "C", 0),
        contents_window=findable and snapshot_full)

    ec.state_bytes = _eval_state_bytes(
        lambda: (window.init_state(), selector.init_state(),
                 limiter.init_state()))
    ec.bucket_ok = bool(window.shape_polymorphic
                        and not selector.extrema_plan)
    ec.compiles = (_ladder_rungs(batch_cap)
                   if ec.bucket_ok and dtypes.config.shape_buckets else 1)
    return ec


def _join_query_cost(node: QueryNode, plan: PlanGraph, registry,
                     batch_cap: int, group_cap: int,
                     name: str) -> ElementCost:
    from ..ops.expr_compile import TypeResolver
    from ..ops.join import multimap_buckets, plan_join
    from ..ops.selector import CompiledSelector
    from ..ops.windows import SlidingWindow, make_layout
    from ..query_api.execution import EventTrigger

    ec = ElementCost(name, "join", node_index=node.index)
    jis: JoinInputStream = node.query.input_stream

    sides = []  # (ins, ref, kind, attrs, window-or-None)
    for ins in (jis.left, jis.right):
        ref = ins.alias or ins.stream_id
        schema = plan.schemas.get(ins.stream_id)
        kind = schema.kind if schema is not None else "stream"
        attrs = _closed(schema.attrs) if schema is not None else None
        if attrs is None:
            ec.exact = False
            ec.notes.append(f"side {ins.stream_id!r}: open schema")
            sides.append((ins, ref, kind, None, None))
            continue
        window = None
        if kind not in ("table", "window", "aggregation"):
            # stream side: its own ring; store-backed sides are priced
            # under their OWN elements (shared state, counted once)
            layout = make_layout(attrs)
            window = _make_window(ins.handlers.window, layout, batch_cap,
                                  True, registry, node.query.annotations)
        sides.append((ins, ref, kind, attrs, window))

    (lins, lref, lkind, lattrs, lwin), (rins, rref, rkind, rattrs, rwin) = sides
    frames = {ref: attrs for _, ref, _, attrs, _ in sides
              if attrs is not None}
    resolver = TypeResolver(frames, lref)

    state_parts = []
    mm_specs = []  # (C, H) per hashable build side
    if lattrs is not None and rattrs is not None and jis.on is not None:
        plan_from_left = plan_join(jis.on, lref, rref, resolver, registry)
        plan_from_right = plan_join(jis.on, rref, lref, resolver, registry)
        for win, plan_as_build in ((lwin, plan_from_right),
                                   (rwin, plan_from_left)):
            if isinstance(win, SlidingWindow) and plan_as_build.probe_keys:
                mm_specs.append((win.C, multimap_buckets(win.C)))

    for win in (lwin, rwin):
        if win is not None:
            state_parts.append(win.init_state)
    if lattrs is not None and rattrs is not None:
        select_all = list(lattrs.items())
        for n, t in rattrs.items():
            if n not in dict(select_all):
                select_all.append((n, t))
        selector = CompiledSelector(
            node.query.selector, resolver, registry, group_cap, lref,
            select_all_attrs=select_all)
        state_parts.append(selector.init_state)
    else:
        selector = None

    def build_state():
        from ..ops.join import multimap_init
        parts = [p() for p in state_parts]
        for cap, buckets in mm_specs:
            parts.append(multimap_init(cap, buckets))
        return tuple(parts)

    ec.state_bytes = _eval_state_bytes(build_state)

    # compiles: one executable per triggering junction-fed probe direction
    # (join steps always run at full batch capacity — no ladder)
    for side_kind, from_left in ((lkind, True), (rkind, False)):
        if side_kind in ("table", "aggregation"):
            continue  # no junction feeds this direction
        triggers = (jis.trigger == EventTrigger.ALL
                    or (jis.trigger == EventTrigger.LEFT and from_left)
                    or (jis.trigger == EventTrigger.RIGHT and not from_left))
        if triggers:
            ec.compiles += 1

    return ec


def _pattern_query_cost(node: QueryNode, plan: PlanGraph, registry,
                        batch_cap: int, group_cap: int,
                        name: str) -> ElementCost:
    import dataclasses as dc

    from ..core import dtypes
    from ..core.pattern_runtime import (_PatternPlan, _RefRewriter,
                                        pending_capacity_of)
    from ..ops.expr_compile import TypeResolver
    from ..ops.selector import CompiledSelector

    ec = ElementCost(name, "pattern", node_index=node.index)
    sis: StateInputStream = node.query.input_stream
    pplan = _PatternPlan(sis, None)
    P = pending_capacity_of(node.query)  # the query's own, not the global

    ref_types: dict[str, dict] = {}
    for pos in pplan.positions:
        for leg in pos.legs:
            schema = plan.schemas.get(leg.stream_id)
            attrs = _closed(schema.attrs) if schema is not None else None
            if attrs is None:
                ec.exact = False
                ec.notes.append(f"leg {leg.stream_id!r}: open schema")
                ec.compiles = 1
                return ec
            ref_types[leg.ref] = attrs

    # --- pending tables (mirror of PatternQueryRuntime._empty_pending) ---
    def captured_refs(pos_index: int) -> list:
        refs = []
        for pos in pplan.positions[:pos_index]:
            for leg in pos.legs:
                refs.append(leg.ref)
        pos = pplan.positions[pos_index]
        if pos.kind == "logical" or (pos.kind == "notand"
                                     and pos.wait_ms is not None):
            for leg in pos.legs:
                refs.append(leg.ref)
        return refs

    total = 0
    for pos_index in range(1, len(pplan.positions)):
        for ref in captured_refs(pos_index):
            total += sum(P * _itemsize(t) for t in ref_types[ref].values())
            total += P * (1 + 8)  # frame_valid + frame_ts
        # start_ts/last_seq/armed_ts (int64) + valid + leg_done[P,2] + origin
        total += P * (8 + 8 + 8 + 1 + 2 + 4)
    ranged = _threshold_table_bytes(pplan, ref_types, registry, node.query,
                                    P)
    if ranged is not None:
        # the key-and-threshold plan's one table (core/pattern_range.py) in
        # place of the tables above, and its `spilled` counter
        total = ranged + 8
        ec.notes.append("key-and-threshold plan: a row of keys' partial "
                        "matches; priced with every attribute of both "
                        "events (the program keeps what the select reads)")
    # active0/seq/dropped/armed0_ts/gate0_seq/expired/live_hwm
    total += 1 + 8 + 8 + 8 + 8 + 8 + 4
    ec.state_bytes = total

    # --- selector over captured frames (rewritten refs, like the runtime) --
    frames = dict(ref_types)
    sid_count: dict[str, int] = {}
    for pos in pplan.positions:
        for leg in pos.legs:
            sid_count[leg.stream_id] = sid_count.get(leg.stream_id, 0) + 1
    for pos in pplan.positions:
        for leg in pos.legs:
            if sid_count[leg.stream_id] == 1 and leg.stream_id not in frames:
                frames[leg.stream_id] = ref_types[leg.ref]
    first_ref = pplan.positions[0].legs[0].ref
    resolver = TypeResolver(frames, first_ref)
    rewriter = _RefRewriter(pplan.count_groups)
    sel = node.query.selector
    sel = dc.replace(
        sel,
        attributes=tuple(
            dc.replace(a, expression=rewriter.rewrite(a.expression))
            for a in sel.attributes),
        having=rewriter.rewrite(sel.having),
        group_by=tuple(rewriter.rewrite(g) for g in sel.group_by))
    select_all, seen = [], set()
    for pos in pplan.positions:
        for leg in pos.legs:
            for n, t in ref_types[leg.ref].items():
                if n not in seen:
                    seen.add(n)
                    select_all.append((n, t))
    selector = CompiledSelector(sel, resolver, registry, group_cap,
                                first_ref, select_all_attrs=select_all)
    ec.state_bytes += _eval_state_bytes(selector.init_state)

    # --- compiles: per-junction steps + the timed heartbeat ---
    sids = {leg.stream_id for pos in pplan.positions for leg in pos.legs}
    merged = pplan.is_sequence and len(sids) > 1
    ec.compiles = 1 if merged else len(sids)
    timed = (pplan.within_ms is not None
             or (pplan.head_group is not None
                 and pplan.head_group.within_ms is not None)
             or any(p.kind == "absent"
                    or (p.kind == "notand" and p.wait_ms is not None)
                    for p in pplan.positions))
    if timed:
        ec.compiles += 1
    return ec


def _threshold_table_bytes(pplan, ref_types: dict, registry, query,
                           pending: int):
    """Bytes of the key-and-threshold plan's table where the query takes
    that plan (core/pattern_runtime.threshold_plan_of), else None: per entry
    of its R x C (the capacity the app states, cut to whole rows) the birth
    sequence, start stamp, threshold, a carried completion's sequence and
    stamp, both events' attributes as 32-bit words; and the key table."""
    from ..core import dtypes
    from ..core.pattern_range import table_shape
    from ..core.pattern_runtime import threshold_plan_of
    from ..ops import slot_table

    by_stream = {leg.stream_id: ref_types[leg.ref]
                 for pos in pplan.positions for leg in pos.legs}
    found = threshold_plan_of(query, by_stream.get, registry)
    if found is None:
        return None
    types, (_, cut, _) = found
    keys, _, R, C, _ = table_shape(
        pending, dtypes.stated_capacity(query.annotations).keys)

    def words(t) -> int:
        return 4 * -(-_itemsize(t) // 4)

    entry = 8 + 8 + words(cut[4]) + 8 + 8 + sum(
        words(t) for attrs in types.values() for t in attrs.values())
    return R * C * entry + 4 * slot_table.ROW * slot_table.buckets_for(keys)


def _named_window_cost(name: str, defn, registry,
                       batch_cap: int) -> ElementCost:
    from ..ops.windows import make_layout

    ec = ElementCost(name, "window")
    attrs = _closed({a.name: a.type for a in defn.attributes})
    if attrs is None:
        ec.exact = False
        ec.notes.append("open schema")
        return ec
    layout = make_layout(attrs)
    window = _make_window(getattr(defn, "window", None), layout, batch_cap,
                          True, registry, defn.annotations)
    ec.state_bytes = _eval_state_bytes(window.init_state)
    if getattr(defn, "window", None) is None:
        ec.notes.append("no window spec: pass-through emission, no "
                        "retained contents")
    ec.notes.append("append step compiles once (untracked jit)")
    return ec


def _table_cost(name: str, defn, group_cap: int, app=None) -> ElementCost:
    from ..core import dtypes
    from ..core.table_index import COUNTERS, index_refusal
    from ..ops.slot_table import ROW, buckets_for

    ec = ElementCost(name, "table")
    if defn.annotations and defn.annotation("store") is not None:
        ec.exact = False
        ec.notes.append("@store record table: rows live host-side (only "
                        "the device cache would count; not modeled)")
        return ec
    cap = dtypes.stated_capacity(defn.annotations).rows \
        or dtypes.config.default_table_capacity
    attrs = {a.name: a.type for a in defn.attributes
             if a.type != AttributeType.OBJECT}
    if any(t is None for t in attrs.values()):
        ec.exact = False
        ec.notes.append("untypeable columns")
        return ec
    # TableState: cols + ts int64[C] + valid bool[C]  (core/table.py)
    ec.state_bytes = cap * (sum(_itemsize(t) for t in attrs.values()) + 8 + 1)
    if app is not None and index_refusal(defn, app) is None:
        # the device index of its key (core/table_index.py): the slot
        # table's buckets, its count and the counters
        ec.state_bytes += 4 * buckets_for(cap) * ROW + 4 + 8 * len(COUNTERS)
        ec.notes.append("@PrimaryKey on the device index: a 128-word "
                        "bucket per 8 stated rows")
    return ec


def _aggregation_cost(name: str, defn, plan: PlanGraph, registry,
                      group_cap: int) -> ElementCost:
    from ..core import dtypes
    from ..extension.registry import ExtensionKind
    from ..ops.aggregators import AggregatorFactory
    from ..query_api.expression import AttributeFunction, Variable

    ec = ElementCost(name, "aggregation")
    in_schema = plan.schemas.get(defn.input_stream_id)
    in_attrs = _closed(in_schema.attrs) if in_schema is not None else None
    durations = tuple(getattr(defn, "durations", ()) or ())
    K = max(group_cap, 4096)
    if in_attrs is None or not durations:
        ec.exact = False
        ec.notes.append("open input schema or no durations: store size "
                        "not statically derivable")
        return ec

    group_attrs = []
    for g in getattr(defn, "group_by", None) or ():
        if isinstance(g, Variable) and g.attribute in in_attrs:
            group_attrs.append(g.attribute)
    typer = ExprTyper({"__in__": in_attrs})
    comp_sizes = []
    for oa in defn.selector.attributes:
        expr = oa.expression
        if isinstance(expr, Variable):
            continue  # group passthrough: stored once under group_cols
        if isinstance(expr, AttributeFunction):
            factory = registry.lookup(ExtensionKind.AGGREGATOR,
                                      expr.namespace, expr.name)
            if isinstance(factory, AggregatorFactory):
                try:
                    arg_types = [typer.type_of(p) or AttributeType.DOUBLE
                                 for p in expr.parameters]
                    spec = factory.make(arg_types)
                    import numpy as np
                    comp_sizes.extend(np.dtype(c.dtype).itemsize
                                      for c in spec.components)
                    continue
                except Exception:
                    pass
        ec.exact = False
        ec.notes.append(f"select item {oa.rename or '?'}: component "
                        "dtypes not statically derivable")
    # DurationStore: key_table(H=2K: int64+int32 +2 scalars) + bucket_ts
    # int64[K] + group_cols + comps + alive bool[K]  (core/aggregation.py)
    per_dur = (2 * K * (8 + 4) + 8
               + 8 * K
               + sum(K * _itemsize(in_attrs[g]) for g in group_attrs)
               + sum(K * s for s in comp_sizes)
               + K)
    ec.state_bytes = per_dur * len(durations)
    ec.notes.append(f"{len(durations)} duration store(s) x K={K} slots")
    return ec


# --------------------------------------------------------------------------
# the whole-app walk
# --------------------------------------------------------------------------


def compute_cost(app_or_plan, *, batch_size: int = 0,
                 group_capacity: int = 0) -> CostReport:
    """Predict the app's device state bytes, compile-ladder size, and
    dispatch classes WITHOUT building a runtime. Per-element failures
    degrade to inexact zero-byte entries (never raise)."""
    from ..core import dtypes
    from ..extension.registry import GLOBAL
    # built-in extension registration side effects (same set the manager
    # imports) — cost analysis must see every window/aggregator factory
    from ..ops import aggregators as _a  # noqa: F401
    from ..ops import builtin_functions as _b  # noqa: F401
    from ..ops import window_factories as _w  # noqa: F401
    from .optimizer import _runtime_names, analyze_sharing

    if isinstance(app_or_plan, PlanGraph):
        plan = app_or_plan
    elif isinstance(app_or_plan, str):
        from .. import compiler
        plan = build_plan(compiler.parse(app_or_plan))
    else:
        plan = build_plan(app_or_plan)
    app = plan.app
    registry = GLOBAL
    batch_cap = int(batch_size) or dtypes.config.default_batch_size
    group_cap = int(group_capacity) or dtypes.config.default_group_capacity

    report = CostReport(app_name=getattr(app, "name", "SiddhiApp"))
    names = _runtime_names(plan)

    # --- queries ---
    for node in plan.queries:
        name = names.get(node.index, node.name)
        ins = node.query.input_stream
        try:
            if isinstance(ins, JoinInputStream):
                ec = _join_query_cost(node, plan, registry, batch_cap,
                                      group_cap, name)
            elif isinstance(ins, StateInputStream):
                ec = _pattern_query_cost(node, plan, registry, batch_cap,
                                         group_cap, name)
            else:
                ec = _single_query_cost(node, plan, registry, batch_cap,
                                        group_cap, name)
        except Exception as e:  # degraded, never fatal
            ec = ElementCost(name, "query", exact=False, compiles=1,
                             node_index=node.index,
                             notes=[f"not statically derivable: {e}"])
        if node.partition is not None:
            keyed = _keyed_partition_bytes(node, plan)
            if keyed is None:
                ec.exact = False
                ec.notes.append("partitioned query: per-key instance "
                                "replication not modeled (lower bound)")
            elif ec.exact:
                # the keyed step: the keys are an axis of the query's one
                # state (core/keyed_partition.py), sized by what the
                # partition states
                ec.state_bytes = keyed
                ec.bucket_ok = True
                ec.notes.append("partition on the keyed step: a ring row "
                                "and table entries per stated key")
        report.elements.append(ec)

    # --- definitions with their own device state ---
    for sid, schema in plan.schemas.items():
        try:
            if schema.kind == "window" and schema.defn is not None:
                report.elements.append(
                    _named_window_cost(sid, schema.defn, registry, batch_cap))
            elif schema.kind == "table" and schema.defn is not None:
                report.elements.append(
                    _table_cost(sid, schema.defn, group_cap, plan.app))
            elif schema.kind == "aggregation" and schema.defn is not None:
                report.elements.append(
                    _aggregation_cost(sid, schema.defn, plan, registry,
                                      group_cap))
        except Exception as e:
            report.elements.append(ElementCost(
                sid, schema.kind, exact=False,
                notes=[f"not statically derivable: {e}"]))

    # --- host-side structures: notes, not device bytes ---
    if app is not None and app.annotation("app:eventTime") is not None:
        report.notes.append("@app:eventTime reorder buffers are host-side "
                            "(bounded by allowed.lateness; not counted)")

    # --- totals + fusion-aware compile ladder ---
    report.state_bytes = sum(e.state_bytes for e in report.elements)
    report.compile_ladder = sum(e.compiles for e in report.elements)
    report.exact = all(e.exact for e in report.elements)

    try:
        opt = analyze_sharing(plan)
    except Exception:
        opt = None
    if opt is not None and opt.enabled and opt.groups:
        by_name = {e.element: e for e in report.elements}
        for g in opt.groups:
            members = [by_name[m] for m in g.members if m in by_name]
            if len(members) < 2:
                continue
            rungs = (_ladder_rungs(batch_cap)
                     if all(m.bucket_ok for m in members)
                     and dtypes.config.shape_buckets else 1)
            report.compile_ladder += rungs - sum(m.compiles for m in members)
            report.fusion.append({"stream": g.stream_id,
                                  "members": list(g.members),
                                  "compiles": rungs})
            for m in members:
                m.notes.append(f"fused into shared step on {g.stream_id!r}")

    # --- superstep dispatch class: with @app:superstep(k=K>1) the eligible
    # plan runs K batches per device dispatch (one lax.scan, one fetch), so
    # the per-event dispatch cost divides by K. What makes a plan
    # superstep-ineligible is SL506's to report (analysis/rules.py). ---
    k = superstep_k(app)
    report.superstep_k = k
    if k > 1:
        for e in report.elements:
            if e.dispatch == "device" and e.kind in ("query", "join"):
                e.dispatch = "superstep"
                e.notes.append(
                    f"superstep k={k}: one device dispatch per {k} "
                    f"micro-batches (per-event dispatch cost / {k}) when "
                    "the plan is eligible at runtime")
        report.notes.append(
            f"superstep k={k}: step dispatches-per-event divide by {k} "
            "for the eligible sub-plan (core/superstep.py)")

    # --- dominant element ---
    if report.state_bytes > 0:
        top = max(report.elements, key=lambda e: e.state_bytes)
        if top.state_bytes * 2 > report.state_bytes:
            report.dominant = top

    # --- shard fleet pricing: @app:shards runs n full pipeline replicas,
    # so the admission-relevant totals multiply by the shard count (shard
    # replica apps have the annotation stripped, so each replica still
    # prices singly and this never compounds) ---
    from .sharding import shard_config
    cfg = shard_config(app)
    if cfg is not None and cfg.n > 1:
        report.state_bytes *= cfg.n
        report.compile_ladder *= cfg.n
        report.notes.append(
            f"x{cfg.n} shard fleet ({cfg.source}): state and compile "
            "ladders price every replica")

    report.budget = app_budget(app)
    return report


def cost_for_plan(plan: PlanGraph) -> CostReport:
    """Per-plan cached cost report (the SL5xx rules all share one walk)."""
    rep = getattr(plan, "_cost_report", None)
    if rep is None:
        rep = compute_cost(plan)
        plan._cost_report = rep
    return rep


def price_splice(app, query, *, batch_size: int = 0,
                 group_capacity: int = 0) -> dict:
    """Incremental re-price for a single-query splice: cost of the app
    WITH `query` attached minus the app as it stands.  Admission control
    (SL501) gates each splice on the *delta* plus the post-splice totals,
    not a whole-app re-admission — a detach therefore frees exactly the
    bytes this predicted.  Returns::

        {"pre": <CostReport dict>, "post": <CostReport dict>,
         "delta_state_bytes": int, "delta_compiles": int}
    """
    import dataclasses as dc
    pre = compute_cost(app, batch_size=batch_size,
                       group_capacity=group_capacity)
    post_app = dc.replace(
        app, execution_elements=list(app.execution_elements) + [query])
    post = compute_cost(post_app, batch_size=batch_size,
                        group_capacity=group_capacity)
    return {
        "pre": pre.to_dict(),
        "post": post.to_dict(),
        "post_state_bytes": post.state_bytes,
        "post_compiles": post.compile_ladder,
        "delta_state_bytes": post.state_bytes - pre.state_bytes,
        "delta_compiles": post.compile_ladder - pre.compile_ladder,
    }


# --------------------------------------------------------------------------
# the live oracle (calibration / statistics deltas)
# --------------------------------------------------------------------------


def _keyed_partition_bytes(node: QueryNode, plan: PlanGraph) -> Optional[int]:
    """The state of a partition's inner query on the keyed step (None: the
    partition stays on the host loop, or its stream's schema is open),
    mirroring ops/keyed_window.KeyedLengthWindow.init_state."""
    from ..core import dtypes
    from ..core.keyed_partition import stated_keys
    from ..ops.keyed_window import KeyedLengthWindow
    from ..ops.slot_table import ROW, buckets_for
    from ..ops.windows import make_layout
    from .rules import partition_engine

    c = node.consumed[0]
    attrs = _closed(getattr(plan.schemas.get(c.stream_id), "attrs", None))
    if attrs is None or partition_engine(node.partition, plan) is not None:
        return None
    keys = stated_keys(node.partition,
                       dtypes.config.default_partition_capacity)
    window = KeyedLengthWindow(
        make_layout(attrs), c.single.handlers.window.parameters[0].value,
        keys)
    return 4 * keys * window.R + 4 * buckets_for(keys) * ROW + 4 + 8


def measure_runtime_state_bytes(rt) -> dict:
    """Live device-state bytes per element on a BUILT runtime — the oracle
    tools/cost_calibrate.py and statistics_report()['cost'] compare the
    static prediction against. Sums .nbytes over each element's state
    pytree (no device sync: nbytes is metadata)."""
    out: dict[str, int] = {}
    for qname, qr in getattr(rt, "query_runtimes", {}).items():
        out[qname] = _tree_bytes(qr.state)
    for wname, w in getattr(rt, "windows", {}).items():
        out[wname] = _tree_bytes(w.state)
    for tname, t in getattr(rt, "tables", {}).items():
        state = getattr(t, "state", None)
        if state is None:
            state = getattr(t, "_state", None)
        out[tname] = _tree_bytes(state) + _tree_bytes(
            getattr(t, "_index", None))
    for aname, a in getattr(rt, "aggregations", {}).items():
        out[aname] = _tree_bytes(a.state)
    return out
