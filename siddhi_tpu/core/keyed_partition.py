"""The keyed step: a value partition whose per-key state is an axis of ONE
state, stepped once a batch (ops/keyed_window.py), in place of the host loop
over the batch's distinct keys (core/partition.py).

Which partitions take it is read from the plan, by `keyed_step_refusal`,
whatever the number of keys: a partition of one stream by one attribute
(int, long, string or bool) around one inner query, that query a plain
`from S[filters]#window.length(L) select ... insert into ...` of current
events whose aggregates are sums and extrema over the window (sum, count,
avg, stdDev, min, max, and, or). The lint rules SL117 / SL118
(analysis/rules.py) tell the author which engine a partition takes from the
same function. Everything else stays on the host loop.

The inner `QueryRuntime` keeps everything around the step — the proxy
junction's dispatch (breaker, debugger, fault routing), callbacks, the
output wiring, warm-up, statistics — and gets a state with a key axis and
the step below in place of its own.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..errors import SiddhiAppCreationError
from ..extension.registry import ExtensionKind
from ..ops.expr_compile import Scope
from ..ops.keyed_window import KeyedLengthWindow
from ..ops.lanes import scatter_lanes
from ..ops.selector import AGG_FRAME
from ..ops.windows import _unpack_rows
from ..query_api.definition import AttributeType
from ..query_api.execution import (
    OutputAction,
    OutputEventType,
    Partition,
    SingleInputStream,
    ValuePartitionType,
)
from ..query_api.expression import (
    AttributeFunction,
    Constant,
    Expression,
    In,
    Variable,
)
from ..telemetry.tracing import StageCells, stage
from .event import EventBatch
from . import dtypes

#: aggregators that are a sum or an extremum over the window's rows
WINDOW_AGGREGATES = frozenset(
    ("sum", "count", "avg", "stdDev", "min", "max", "and", "or"))
KEY_TYPES = (AttributeType.INT, AttributeType.LONG, AttributeType.STRING,
             AttributeType.BOOL)
#: rows of ring a partition may state in all
MAX_RING_ROWS = 2 ** 30
#: the longest window the step takes: its append costs B * L * L selects
MAX_LENGTH = 64


def _walk(e):
    yield e
    for name in ("left", "right", "expression"):
        sub = getattr(e, name, None)
        if isinstance(sub, Expression):
            yield from _walk(sub)
    for p in getattr(e, "parameters", ()) or ():
        if isinstance(p, Expression):
            yield from _walk(p)


def keyed_step_refusal(partition: Partition, attribute_types,
                       registry) -> Optional[str]:
    """None where the partition takes the keyed step, else why it stays on
    the host loop. `attribute_types(stream_id)` gives the stream's
    attribute types by name (None: no such stream)."""
    if len(partition.partition_types) != 1:
        return "it partitions more than one stream"
    (ptype,) = partition.partition_types
    if not isinstance(ptype, ValuePartitionType):
        return "it is a range partition"
    if any(a.name.lower() == "purge" for a in partition.annotations or ()):
        return "@purge lets go of idle keys"
    if len(partition.queries) != 1:
        return "it holds more than one inner query"
    (query,) = partition.queries
    ins = query.input_stream
    if not isinstance(ins, SingleInputStream):
        return "its inner query is a join or a pattern"
    if ins.is_inner or ins.is_fault or ins.stream_id != ptype.stream_id:
        return ("its inner query reads a stream the partition does not key "
                f"({ins.stream_id!r})")
    types = attribute_types(ptype.stream_id)
    key = ptype.expression
    if types is None or not isinstance(key, Variable) \
            or types.get(key.attribute) not in KEY_TYPES:
        return ("its key is not one int, long, string or bool attribute of "
                "the stream")
    h = ins.handlers
    w = h.window
    if w is None or w.namespace or w.name != "length":
        return "its inner query's window is not #window.length(L)"
    length = w.parameters[0] if len(w.parameters) == 1 else None
    if not isinstance(length, Constant) or not isinstance(length.value, int) \
            or not 1 <= length.value <= MAX_LENGTH:
        return (f"its window is not a length of 1 to {MAX_LENGTH} rows (the "
                "step lays a key's rows of the batch over its ring row by "
                "row)")
    if h.pre_window_functions or h.post_window_functions \
            or h.post_window_filters:
        return "its inner query has stream functions or a post-window filter"
    if any(isinstance(n, In) for f in h.filters for n in _walk(f)):
        return "its inner query's filter probes a table"
    sel = query.selector
    if sel.group_by or sel.order_by or sel.limit is not None \
            or sel.offset is not None:
        return "its inner query groups, orders or limits"
    for e in [a.expression for a in sel.attributes] + (
            [sel.having] if sel.having is not None else []):
        for n in _walk(e):
            if not isinstance(n, AttributeFunction):
                continue
            if n.name == "UUID" and not n.namespace:
                return "its inner query selects UUID()"
            if registry.lookup(ExtensionKind.AGGREGATOR, n.namespace,
                               n.name) is not None \
                    and (n.namespace or n.name not in WINDOW_AGGREGATES):
                return (f"{n.name}() is not a sum or an extremum over the "
                        "window")
    out = query.output_stream
    if out.action != OutputAction.INSERT or out.is_inner or out.is_fault \
            or out.event_type != OutputEventType.CURRENT:
        return ("its inner query does not insert current events into an "
                "outer stream")
    if query.output_rate is not None:
        return "its inner query limits its output rate"
    return None


def stated_keys(partition: Partition, default: int) -> int:
    """`@capacity(keys='N')` on the partition, else the app's
    `partition_capacity` (or the process default)."""
    stated = dtypes.stated_capacity(partition.annotations).keys
    return default if stated is None else stated


class KeyedStep:
    """The key axis in the inner query's state, and the partition's account
    (`statistics_report()["partitions"][name]`)."""

    def __init__(self, partition_runtime, spec, qr) -> None:
        from ..ops.windows import SlidingWindow
        self.name = partition_runtime.name
        self.qr = qr
        assert isinstance(qr.window, SlidingWindow) \
            and qr.window.length is not None and qr.window.time_ms is None
        self.capacity = stated_keys(
            partition_runtime.partition,
            partition_runtime.ctx.effective_partition_capacity)
        length = qr.window.length
        if self.capacity * length > MAX_RING_ROWS:
            raise SiddhiAppCreationError(
                f"partition {self.name!r}: {self.capacity:,} keys of "
                f"#window.length({length}) are {self.capacity * length:,} "
                f"rows of ring, more than {MAX_RING_ROWS:,}")
        self.window = KeyedLengthWindow(qr.window.layout, length,
                                        self.capacity)
        stats = jax.devices()[0].memory_stats() or {}
        limit = int(stats.get("bytes_limit", 16 << 30))
        ring = 4 * self.window.R * self.capacity
        if ring > limit:
            raise SiddhiAppCreationError(
                f"partition {self.name!r}: a ring of {self.capacity:,} keys "
                f"x {self.window.R} words ({length} rows of "
                f"{self.window.W} and a count, in whole tiles) is {ring:,} "
                f"bytes, more than the device's {limit:,}")
        self._key = spec.value_raw
        qr.take_step(self._make_step(), (self.window.init_state(), (), ()))
        self.cells = StageCells(("step", "drop_sync"))
        self.steps = 0
        self.synced = {"keys": 0, "keys_dropped": 0}
        self._drop_warned = False

    # ------------------------------------------------------------------ step

    def _make_step(self):
        qr, window, key_of = self.qr, self.window, self._key
        filters, selector, frame_ref = qr.filters, qr.selector, qr.frame_ref
        layout = window.layout
        stats, qname = qr.ctx.statistics, qr.name

        def aggregates(w, s_cols, s_ts):
            """Every aggregator slot's value per lane, in (slot, lane)
            order: each component reduced over the lane's window."""
            L, B = window.L, s_ts.shape[0]
            # the held rows word by word, [L, B] a word, then flat: the
            # compiled expressions take columns
            held_cols, held_ts = _unpack_rows(w.held.transpose(1, 0, 2),
                                              layout)
            held_cols = {k: v.reshape(L * B) for k, v in held_cols.items()}
            held_ts = held_ts.reshape(L * B)
            scopes = []
            for cols, ts in ((held_cols, held_ts), (s_cols, s_ts)):
                sc = Scope()
                sc.add_frame(frame_ref, cols, ts,
                             jnp.ones(ts.shape, bool), default=True)
                scopes.append(sc)
            values = {}
            for slot_name, spec, args in selector.agg_specs:
                held_arg, batch_arg = (
                    args[0](sc) if args else None for sc in scopes)
                parts = []
                for comp in spec.components:
                    held_d, batch_d = (
                        jnp.broadcast_to(comp.delta(
                            arg, jnp.ones((n,), jnp.int32)).astype(
                                comp.dtype), (n,))
                        for arg, n in ((held_arg, L * B), (batch_arg, B)))
                    parts.append(window.window_reduce(
                        w, held_d.reshape(L, B), batch_d, comp.op))
                values[slot_name] = spec.finalize(parts)
            return values

        def step(state, batch: EventBatch, now, table_states=None):
            stats.track_compile(qname, batch.capacity)
            wstate = state[0]
            scope = Scope()
            scope.add_frame(frame_ref, batch.cols, batch.ts, batch.valid,
                            default=True)
            scope.extras["now"] = now
            with stage("filter"):
                mask = batch.valid
                for f in filters:
                    mask = mask & f(scope)
                batch = batch.where_valid(mask)
                scope.add_frame(frame_ref, batch.cols, batch.ts, batch.valid,
                                default=True)
            with stage("window"):
                wstate, w = window.fetch(wstate, key_of(batch), batch)
            with stage("selector"):
                s_cols, s_ts = _unpack_rows(w.rows, layout)
                agg_sorted = aggregates(w, s_cols, s_ts)
            with stage("window"):
                wstate = window.append(wstate, w)
            with stage("emit"):
                # the aggregates back in lane order: `order` is a
                # permutation, so an 8-byte value's words may go apart
                agg_values = {
                    slot: scatter_lanes(jnp.zeros_like(v), w.order, v)
                    for slot, v in agg_sorted.items()}
                live = w.lane_live
            with stage("selector"):
                if selector.agg_specs:
                    scope.frames[AGG_FRAME] = agg_values
                    scope.valids[AGG_FRAME] = live
                    scope.ts[AGG_FRAME] = batch.ts
                out_cols = {}
                for name, ce in selector.out_exprs:
                    v = ce(scope)
                    out_cols[name] = jnp.broadcast_to(v, batch.ts.shape) \
                        if jnp.ndim(v) == 0 else v
                if selector.having is not None:
                    scope.frames["__out__"] = out_cols
                    scope.valids["__out__"] = live
                    scope.ts["__out__"] = batch.ts
                    live = live & selector.having(scope)
            out = EventBatch(ts=batch.ts, cols=out_cols, valid=live,
                             types=jnp.zeros(batch.ts.shape, jnp.int8))
            return (wstate, (), ()), out

        return step

    # --------------------------------------------------------------- account

    def route(self, proxy, batch: EventBatch, now: int) -> None:
        """One step for the whole batch, through the proxy junction the
        inner query subscribes to."""
        with self.cells.span("step", "siddhi.partition.step"):
            proxy.publish_batch(batch, now)
        self.steps += 1
        if not self._drop_warned and self.steps % 64 == 0:
            # a device sync under the controller lock, as the join's and the
            # pattern's: it waits for every step dispatched so far
            with self.cells.span("drop_sync", "siddhi.partition.drop_sync"):
                self.sync_counters(jax.device_get(self.device_counters()))

    def device_counters(self) -> dict:
        """Copies of the state's counters for collect_overflow()'s one
        fetch (under the controller lock: the next step donates the
        state)."""
        ws = self.qr.state[0]
        return {"keys": jnp.copy(ws.table.count),
                "keys_dropped": jnp.copy(ws.dropped)}

    def sync_counters(self, fetched: dict) -> None:
        self.synced.update({k: int(v) for k, v in fetched.items()})
        if self.synced["keys_dropped"] and not self._drop_warned:
            import warnings
            warnings.warn(
                f"partition {self.name!r}: {self.synced['keys_dropped']} "
                f"events of keys beyond its {self.capacity} key slots were "
                "dropped — raise @capacity(keys=...) on the partition",
                stacklevel=2)
            self._drop_warned = True

    def stats_snapshot(self) -> dict:
        """statistics_report()["partitions"][name]. `steps` and `out_lanes`
        (the out block's lanes, valid or not: what the read-back fetches)
        are cumulative; `keys` (slots taken) and `keys_dropped` (lanes
        whose key found no slot) are the device's as last synced: at a
        report, and every 64th step."""
        return {
            "query": self.qr.name,
            "capacity": self.capacity,
            "length": self.window.L,
            "steps": self.steps,
            "out_lanes": self.qr._out_lanes,
            **self.synced,
            "stage_ms": self.cells.snapshot(),
        }
