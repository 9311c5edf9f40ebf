"""Partitions — `partition with (<key> of Stream, ...) begin <queries> end`.

Reference: core/partition/ — PartitionRuntimeImpl.java:75 (per-key clones of
the inner queries + inner `#stream` junctions), PartitionStreamReceiver.java:44
(evaluates a PartitionExecutor per event, lazily clones query runtimes per key,
routes via key-suffixed junctions), ValuePartitionExecutor /
RangePartitionExecutor, PartitionStateHolder (per-key state keyed by
thread-local flow id), `@purge` idle-key cleanup (PartitionRuntimeImpl:120-136).

TPU re-design — clone STATE, never code: the reference clones whole
QueryRuntime object graphs per key; here every inner query is planned and
jit-compiled exactly ONCE. How a key's state is kept, and how many steps a
batch costs, follows from the partition's plan, one engine per job:

- **stateless** inner graph (pure filter/projection — the BASELINE
  partitioned-filter shape): no per-key state, so one fused pass over the
  whole batch is semantically identical and runs at full batch width.
- **the keyed step** (core/keyed_partition.py, `self.keyed`): one stream
  partitioned by one attribute around one `#window.length(L)` query. The
  key is an AXIS of the inner query's state (one ring of K keys x L rows
  and an exact key table on the device, ops/keyed_window.py) and the
  receiver dispatches ONE step a batch through the proxy junction,
  whatever the number of keys; `@capacity(keys=...)` on the partition
  states K. For these shapes the host loop below is not reachable.
- **the mesh step** (parallel/sharded.PartitionedQueryStep), only where the
  app runs on a device mesh: the step vmapped over a slot axis.
- **the host loop**, everything else (range partitions, several inner
  queries or `#inner` streams, joins and patterns inside, time-driven
  windows, `@purge`, a global stream feeding the partition): a partition
  key owns a pytree of state (window rings + group tables) swapped into the
  shared compiled step. Keys cost state memory, not compile time — and one
  dispatch of every inner step per distinct key per batch: the batch's key
  values are fetched to the host, and the batch is split into per-key masked
  views (capacity unchanged — lanes outside the key are invalid).
  `engine_reason` says why a stateful partition is here; lint rule SL117
  tells the author at build.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import DefinitionNotExistError, SiddhiAppCreationError
from ..ops.expr_compile import Scope, TypeResolver, compile_expression
from ..query_api.definition import AttributeType
from ..query_api.execution import (
    JoinInputStream,
    OutputAction,
    Partition,
    Query,
    RangePartitionType,
    SingleInputStream,
    StateInputStream,
    ValuePartitionType,
)
from .event import EventBatch
from .stream import Receiver, StreamJunction


_TIME_UNITS_MS = {
    "millisecond": 1, "milliseconds": 1, "ms": 1,
    "second": 1000, "seconds": 1000, "sec": 1000,
    "minute": 60_000, "minutes": 60_000, "min": 60_000,
    "hour": 3_600_000, "hours": 3_600_000,
    "day": 86_400_000, "days": 86_400_000,
    "month": 2_592_000_000, "months": 2_592_000_000,
    "year": 31_536_000_000, "years": 31_536_000_000,
}


def _parse_annotation_time(text: str) -> int:
    """Annotation time strings like '1 hour', '10 sec', '5000' → ms
    (reference: SiddhiConstants purge annotation values)."""
    parts = text.strip().lower().split()
    if len(parts) == 1:
        return int(parts[0])
    if len(parts) % 2 != 0:
        raise SiddhiAppCreationError(f"bad time literal {text!r}")
    total = 0
    for i in range(0, len(parts), 2):
        unit = _TIME_UNITS_MS.get(parts[i + 1])
        if unit is None:
            raise SiddhiAppCreationError(f"bad time literal {text!r}")
        total += int(parts[i]) * unit
    return total


def _referenced_streams(query: Query):
    """(stream_id, is_inner) pairs consumed by a query."""
    ins = query.input_stream
    if isinstance(ins, SingleInputStream):
        return [(ins.stream_id, ins.is_inner)]
    if isinstance(ins, JoinInputStream):
        return [(ins.left.stream_id, ins.left.is_inner),
                (ins.right.stream_id, ins.right.is_inner)]
    if isinstance(ins, StateInputStream):
        out = []

        def walk(el):
            from ..query_api.execution import (
                AbsentStreamStateElement,
                CountStateElement,
                EveryStateElement,
                LogicalStateElement,
                NextStateElement,
                StreamStateElement,
            )
            if isinstance(el, StreamStateElement):
                out.append((el.stream.stream_id, el.stream.is_inner))
            elif isinstance(el, AbsentStreamStateElement):
                out.append((el.stream.stream_id, el.stream.is_inner))
            elif isinstance(el, NextStateElement):
                walk(el.state)
                walk(el.next)
            elif isinstance(el, EveryStateElement):
                walk(el.state)
            elif isinstance(el, LogicalStateElement):
                walk(el.left)
                walk(el.right)
            elif isinstance(el, CountStateElement):
                walk(el.element)

        walk(ins.state)
        return out
    return []


class _KeySpec:
    """Compiled partition-key extraction for one partitioned stream."""

    def __init__(self, ptype, junction, registry) -> None:
        definition = junction.definition
        sid = definition.id
        attr_types = {a.name: a.type for a in definition.attributes
                      if a.type != AttributeType.OBJECT}
        resolver = TypeResolver({sid: attr_types}, sid, {sid: junction.codec})
        self.is_range = isinstance(ptype, RangePartitionType)
        if self.is_range:
            self.ranges = []  # (key_string, jitted bool fn)
            for rp in ptype.ranges:
                cond = compile_expression(rp.condition, resolver, registry)
                if cond.type != AttributeType.BOOL:
                    raise SiddhiAppCreationError(
                        f"range partition condition for {rp.partition_key!r} "
                        "must be boolean")
                self.ranges.append((rp.partition_key, self._jit(cond, sid)))
        else:
            executor = compile_expression(ptype.expression, resolver, registry)
            #: un-jitted batch→key-values closure, traceable inside larger
            #: jits (the mesh partition step); value_fn is its jitted form
            self.value_raw = self._wrap(executor, sid)
            self.value_fn = jax.jit(self.value_raw)

    @staticmethod
    def _wrap(executor, sid):
        def fn(batch: EventBatch):
            scope = Scope()
            scope.add_frame(sid, batch.cols, batch.ts, batch.valid, default=True)
            return executor(scope)

        return fn

    @classmethod
    def _jit(cls, executor, sid):
        return jax.jit(cls._wrap(executor, sid))


class PartitionRuntime:
    """One `partition ... begin ... end` block."""

    def __init__(self, partition: Partition, app_runtime, index: int) -> None:
        self.partition = partition
        self.rt = app_runtime
        self.ctx = app_runtime.ctx
        self.name = f"partition{index}"

        # --- key extraction per partitioned stream ---
        self.key_specs: dict[str, _KeySpec] = {}
        for pt in partition.partition_types:
            sid = pt.stream_id
            junction = app_runtime.junctions.get(sid)
            if junction is None:
                raise DefinitionNotExistError(
                    f"partition stream {sid!r} is not defined")
            if sid in self.key_specs:
                raise SiddhiAppCreationError(
                    f"stream {sid!r} partitioned twice in one partition")
            self.key_specs[sid] = _KeySpec(pt, junction, self.ctx.registry)

        # --- inner graph: proxies for outer streams, junctions for #streams ---
        self.proxies: dict[str, StreamJunction] = {}
        self.inner_junctions: dict[str, StreamJunction] = {}
        self.runtimes: dict[str, object] = {}
        self._build_inner_queries()

        # --- per-key state instances ---
        self.template_states = {name: qr.state
                                for name, qr in self.runtimes.items()}
        self.stateless = all(self._is_stateless(qr)
                             for qr in self.runtimes.values())
        self.instances: dict = {}  # key -> {qname: state pytree}
        self.last_seen: dict = {}  # key -> last routed ts
        self._active_key = None  # reentrancy guard for _run_keyed
        self._purge_idle_ms: Optional[int] = None
        ann = next((a for a in partition.annotations or ()
                    if a.name.lower() == "purge"), None)
        if ann is not None:
            idle = ann.element("idle.period") or ann.element("idlePeriod")
            if idle:
                self._purge_idle_ms = _parse_annotation_time(idle)

        # --- mesh-sharded execution (key-slot axis), when eligible ---
        self._mesh_step = None
        self._init_mesh_path()
        # --- the keyed step: the key as an axis of the inner query's state
        # (core/keyed_partition.py), for the shapes its plan check takes ---
        self.keyed = None
        self.engine_reason = None
        if self._mesh_step is None and not self.stateless:
            self._init_keyed_step()

        # --- routing subscriptions ---
        for sid, proxy in self.proxies.items():
            outer = app_runtime.junctions[sid]
            if sid in self.key_specs:
                outer.subscribe(_PartitionStreamReceiver(self, sid))
            else:
                outer.subscribe(_GlobalStreamReceiver(self, sid))

    # ------------------------------------------------------------------- mesh

    def _init_mesh_path(self) -> None:
        """Swap the per-key host loop for one SPMD step over a key-slot axis
        (parallel/sharded.PartitionedQueryStep) when a mesh is configured and
        the partition shape supports it: a single value-partitioned stream
        feeding a single plain query. Range partitions, joins/patterns,
        inner `#streams`, `in Table` deps, and `@purge` (slot states are
        permanent) stay on the host loop."""
        mesh = getattr(self.ctx, "mesh", None)
        if mesh is None or self.stateless:
            return
        if self._purge_idle_ms is not None:
            return
        if len(self.key_specs) != 1 or len(self.runtimes) != 1:
            return
        if self.inner_junctions or set(self.proxies) != set(self.key_specs):
            return
        from .query_runtime import QueryRuntime

        ((sid, spec),) = self.key_specs.items()
        ((_, qr),) = self.runtimes.items()
        if spec.is_range or not isinstance(qr, QueryRuntime) or qr.dep_tables:
            return

        from ..ops.groupby import hash_columns
        from ..parallel.sharded import PartitionedQueryStep

        axis = mesh.axis_names[0]
        n_slots = self.ctx.effective_partition_capacity

        def key_fn(batch: EventBatch):
            return hash_columns([spec.value_raw(batch)])

        self._mesh_step = PartitionedQueryStep(
            qr._make_step(), mesh, axis, n_slots, key_fn)
        self._mesh_states, self._mesh_keys = self._mesh_step.init_state(
            qr._init_state())
        self._mesh_qr = qr
        self._mesh_sid = sid
        self._mesh_batches = 0
        self._mesh_key_warned = False

    def _init_keyed_step(self) -> None:
        """One path per job, decided from the plan whatever the number of
        keys: where `keyed_step_refusal` finds nothing the host loop below
        is not reachable; where it does, `engine_reason` says what."""
        from .keyed_partition import KeyedStep, keyed_step_refusal

        def attribute_types(sid):
            j = self.rt.junctions.get(sid)
            return None if j is None or sid in self.rt.windows else {
                a.name: a.type for a in j.definition.attributes}

        self.engine_reason = keyed_step_refusal(
            self.partition, attribute_types, self.ctx.registry)
        if self.engine_reason is None:
            ((_, spec),) = self.key_specs.items()
            ((_, qr),) = self.runtimes.items()
            self.keyed = KeyedStep(self, spec, qr)

    def _mesh_route(self, batch: EventBatch, now: int) -> None:
        import time as _time

        qr = self._mesh_qr
        t0 = _time.perf_counter_ns()
        debugger = getattr(self.ctx, "debugger", None)
        if debugger is not None:
            from .debugger import QueryTerminal
            if debugger.wants(qr.name, QueryTerminal.IN):
                debugger.check_break_point(
                    qr.name, QueryTerminal.IN, batch.to_host_events(qr.codec))
        self._mesh_states, self._mesh_keys, out = self._mesh_step(
            self._mesh_states, self._mesh_keys, batch, now)
        qr._distribute(out, now)
        self.ctx.statistics.track_latency(qr.name, _time.perf_counter_ns() - t0)
        self._mesh_batches += 1
        # key-slot occupancy: checked every batch (the _distribute host fetch
        # already synced the device, so reading count is cheap). Keys that
        # arrive past capacity get slot ids >= n_slots, matching no device
        # slot — their events are DROPPED, and a later small-hash key can
        # evict a live key's table entry (ops/groupby.py sorted merge).
        if not self._mesh_key_warned:
            used = int(self._mesh_keys.count)
            cap = self._mesh_step.n_slots
            if used >= cap:
                import warnings
                warnings.warn(
                    f"partition {self.name!r}: all {cap} key slots used — "
                    "events for any further partition keys are dropped; "
                    "raise partition_capacity", stacklevel=2)
                self._mesh_key_warned = True
        if (self._mesh_qr._has_custom_aggs
                and (self._mesh_batches in (1, 16, 64)
                     or self._mesh_batches % 256 == 0)):
            self._check_mesh_agg_capacity()

    def _check_mesh_agg_capacity(self) -> None:
        """Per-slot distinctCount pair tables overflow independently; warn on
        the fullest slot (mirrors QueryRuntime._check_custom_agg_capacity)."""
        import warnings

        from ..ops.groupby import GroupState, KeyTable
        for g in self._mesh_states[1].groups:
            if not (isinstance(g, tuple) and g):
                continue
            if isinstance(g[0], KeyTable):
                kt = g[0]
                cap = kt.keys.shape[-1] // 2  # hash array is 2x id capacity
                worst = int(np.max(np.asarray(kt.count)))
                if worst > int(0.85 * cap):
                    warnings.warn(
                        f"partition {self.name!r}: a key slot's distinctCount "
                        f"pair table is at {worst}/{cap} lifetime-unique "
                        "pairs; counts will corrupt past capacity — raise "
                        "group_capacity", stacklevel=2)
                elif int(np.max(np.asarray(kt.misses))) > 0:
                    warnings.warn(
                        f"partition {self.name!r}: key lookups exhausted "
                        "their hash probe window and aliased group 0 — raise "
                        "group_capacity", stacklevel=2)
            elif isinstance(g[0], GroupState) and len(g) == 2:
                # string-code fast path: pair table indexed by interning code
                cap = g[0].values.shape[-1]
                n_codes = len(self.ctx.global_strings)
                if n_codes > int(0.85 * cap):
                    warnings.warn(
                        f"partition {self.name!r}: distinctCount code table "
                        f"at {n_codes}/{cap} interned strings; codes past "
                        "capacity are dropped from the count — raise "
                        "group_capacity", stacklevel=2)

    # ------------------------------------------------------------------ build

    def _proxy_for(self, sid: str) -> StreamJunction:
        if sid not in self.proxies:
            outer = self.rt.junctions.get(sid)
            if outer is None:
                raise DefinitionNotExistError(
                    f"stream {sid!r} (used in partition) is not defined")
            proxy = StreamJunction(outer.definition, self.ctx, codec=outer.codec)
            # @OnError(action='STREAM') failures inside the partition route to
            # the same !stream as outside it
            proxy.fault_junction = outer.fault_junction
            self.proxies[sid] = proxy
        return self.proxies[sid]

    def _resolve_input(self, sid: str, is_inner: bool) -> StreamJunction:
        if is_inner:
            j = self.inner_junctions.get(sid)
            if j is None:
                raise DefinitionNotExistError(
                    f"inner stream #{sid} consumed before any query inserts "
                    "into it (order inner queries producer-first)")
            return j
        if sid in self.rt.windows:
            return self.rt.windows[sid].output_junction
        return self._proxy_for(sid)

    def _build_inner_queries(self) -> None:
        from .join_runtime import JoinQueryRuntime, _JoinSideReceiver
        from .pattern_runtime import PatternQueryRuntime, _PatternSideReceiver
        from .query_runtime import QueryRuntime

        rt = self.rt
        for i, query in enumerate(self.partition.queries):
            name = query.name or f"{self.name}_query{i + 1}"
            refs = _referenced_streams(query)
            # resolve inputs through proxies/inner junctions
            jmap = {}
            for sid, is_inner in refs:
                if sid in rt.tables or sid in rt.aggregations:
                    continue
                jmap[sid] = self._resolve_input(sid, is_inner)

            ins = query.input_stream
            if isinstance(ins, JoinInputStream):
                qr = JoinQueryRuntime(query, self.ctx, jmap, rt.tables,
                                      self.ctx.registry, name,
                                      windows=rt.windows,
                                      aggregations=rt.aggregations)
                if qr.left.junction is not None:
                    qr.left.junction.subscribe(_JoinSideReceiver(qr, True))
                if qr.right.junction is not None:
                    qr.right.junction.subscribe(_JoinSideReceiver(qr, False))
            elif isinstance(ins, StateInputStream):
                qr = PatternQueryRuntime(query, self.ctx, jmap, rt.tables,
                                         self.ctx.registry, name)
                for sid in qr.junctions:
                    qr.junctions[sid].subscribe(_PatternSideReceiver(qr, sid))
            elif isinstance(ins, SingleInputStream):
                junction = jmap.get(ins.stream_id)
                if junction is None:
                    raise DefinitionNotExistError(
                        f"stream {ins.stream_id!r} is not defined")
                qr = QueryRuntime(query, self.ctx, junction, self.ctx.registry,
                                  name=name, tables=rt.tables)
                junction.subscribe(qr)
            else:
                raise SiddhiAppCreationError(
                    f"{type(ins).__name__} queries are not supported in partitions")

            self._wire_inner_output(qr, query)
            qr._partitioned = True  # app-level heartbeat must not drive these
            self.runtimes[name] = qr
            rt.query_runtimes[name] = qr  # query callbacks reach inner queries

    def _wire_inner_output(self, qr, query: Query) -> None:
        out = query.output_stream
        if out.action == OutputAction.INSERT and out.target_id:
            if out.is_inner:
                # `insert into #Inner` — partition-scoped stream; schema comes
                # from the producing query (reference: PartitionRuntimeImpl:85)
                j = self.inner_junctions.get(out.target_id)
                if j is None:
                    j = StreamJunction(qr.output_definition, self.ctx,
                                       codec=qr.output_codec)
                    self.inner_junctions[out.target_id] = j
                qr.output_junction = j
                return
        # outer targets (streams/tables/windows) exit the partition
        self.rt._wire_output(qr, query)

    @staticmethod
    def _is_stateless(qr) -> bool:
        from ..ops.ratelimit import PassThroughLimiter
        from ..ops.windows import PassThroughWindow
        from .query_runtime import QueryRuntime

        if not isinstance(qr, QueryRuntime):
            return False  # joins/patterns always keep state
        return (isinstance(qr.window, PassThroughWindow)
                and not qr.selector.agg_specs
                and not (qr.query.selector.group_by or ())
                and isinstance(qr.rate_limiter, PassThroughLimiter))

    # ---------------------------------------------------------------- routing

    def _instance(self, key):
        inst = self.instances.get(key)
        if inst is None:
            # fresh per-key buffers: steps donate their state args, so
            # instances must never alias the template (or each other)
            inst = {name: jax.tree_util.tree_map(jnp.copy,
                                                 self.template_states[name])
                    for name in self.runtimes}
            self.instances[key] = inst
        return inst

    def route(self, sid: str, batch: EventBatch, now: int) -> None:
        if self._mesh_step is not None:
            self._mesh_route(batch, now)
            return
        proxy = self.proxies[sid]
        if self.keyed is not None:
            self.keyed.route(proxy, batch, now)
            return
        spec = self.key_specs[sid]
        if self.stateless and not spec.is_range:
            # value partitions: every valid event has a key, and with no
            # per-key state one full-width pass is semantically identical
            proxy.publish_batch(batch, now)
            return
        valid = np.asarray(batch.valid)
        if not valid.any():
            # timer batch: heartbeat every live instance so time windows fire
            for key in list(self.instances):
                self._run_keyed(key, lambda: proxy.publish_batch(batch, now))
            return
        if spec.is_range:
            # events matching no range are dropped (reference:
            # PartitionStreamReceiver — a null key routes nowhere)
            for key, fn in spec.ranges:
                mask = np.asarray(fn(batch)) & valid
                if mask.any():
                    sub = dataclasses.replace(batch, valid=jnp.asarray(mask))
                    self.last_seen[key] = now
                    self._run_keyed(key, lambda s=sub: proxy.publish_batch(s, now))
            return
        keys = np.asarray(spec.value_fn(batch))
        for key in np.unique(keys[valid]).tolist():
            mask = (keys == key) & valid
            sub = dataclasses.replace(batch, valid=jnp.asarray(mask))
            self.last_seen[key] = now
            self._run_keyed(key, lambda s=sub: proxy.publish_batch(s, now))

    def broadcast(self, sid: str, batch: EventBatch, now: int) -> None:
        """Non-partitioned stream feeding inner queries: goes to every live
        key instance (reference: PartitionStreamReceiver broadcast path)."""
        proxy = self.proxies[sid]
        if self.stateless:
            proxy.publish_batch(batch, now)
            return
        for key in list(self.instances):
            self._run_keyed(key, lambda: proxy.publish_batch(batch, now))

    def _run_keyed(self, key, action: Callable) -> None:
        # re-entrancy: an inner query inserting into an outer stream consumed
        # by this same partition re-enters here synchronously. Same key →
        # states are already live, run in place; different key → push/pop so
        # the active key's mid-batch state survives the nested run.
        if self._active_key is not None and key == self._active_key:
            action()
            return
        inst = self._instance(key)
        prev_states = {name: qr.state for name, qr in self.runtimes.items()}
        prev_key, self._active_key = self._active_key, key
        for name, qr in self.runtimes.items():
            qr.state = inst[name]
        try:
            action()
        finally:
            for name, qr in self.runtimes.items():
                inst[name] = qr.state
                qr.state = prev_states[name]
            self._active_key = prev_key

    # ----------------------------------------------------------------- timers

    def heartbeat(self, now: int) -> None:
        if self._mesh_step is not None:
            # one all-invalid batch heartbeats every key slot on device
            proxy = self.proxies[self._mesh_sid]
            empty = EventBatch.empty(proxy.definition, proxy.batch_size)
            self._mesh_route(empty, now)
            return
        if self.keyed is not None:
            return  # a length window keeps no time; no key is ever let go
        if self._purge_idle_ms is not None:
            cutoff = now - self._purge_idle_ms
            for key in [k for k, ts in self.last_seen.items() if ts < cutoff]:
                self.instances.pop(key, None)
                self.last_seen.pop(key, None)
        if self.stateless:
            return
        for key in list(self.instances):
            self._run_keyed(
                key, lambda: [j.heartbeat(now) for j in self.proxies.values()])

    @property
    def has_time_semantics(self) -> bool:
        return any(getattr(qr, "has_time_semantics", False)
                   for qr in self.runtimes.values())

    # --------------------------------------------------------------- snapshot

    def snapshot_states(self, fetch: Optional[Callable] = None,
                        prefix: str = ""):
        """`fetch(key, state)` is SnapshotService's identity-memoized
        device-delta fetch; standalone callers get a plain host copy."""
        from ..state.persistence import _to_host
        if fetch is None:
            fetch = lambda _k, s: _to_host(s)  # noqa: E731

        if self._mesh_step is not None:
            return {"__mesh_states__": fetch(prefix + "ms", self._mesh_states),
                    "__mesh_keys__": fetch(prefix + "mk", self._mesh_keys)}
        if self.keyed is not None:
            return {"__keyed__": fetch(prefix + "k", self.keyed.qr.state)}
        return {repr(k): {n: fetch(f"{prefix}{k!r}:{n}", s)
                          for n, s in inst.items()}
                for k, inst in self.instances.items()}

    def restore_states(self, snap) -> None:
        import ast

        from ..errors import CannotRestoreStateError
        from ..state.persistence import _to_device
        if self._mesh_step is not None:
            if set(snap) != {"__mesh_states__", "__mesh_keys__"}:
                raise CannotRestoreStateError(
                    "snapshot was taken without a mesh; cannot restore into a "
                    "mesh-sharded partition (or vice versa)")
            self._mesh_states = _to_device(
                snap["__mesh_states__"], self._mesh_states)
            self._mesh_keys = _to_device(snap["__mesh_keys__"], self._mesh_keys)
            return
        if self.keyed is not None:
            if set(snap) != {"__keyed__"}:
                raise CannotRestoreStateError(
                    "snapshot was taken on the host loop (a state per key); "
                    "this partition keeps its keys as an axis of one state")
            qr = self.keyed.qr
            qr.state = _to_device(snap["__keyed__"], qr.state)
            return
        self.instances = {}
        now = self.ctx.timestamp_generator.current_time()
        for k_repr, inst in snap.items():
            key = ast.literal_eval(k_repr)  # int/float/str keys only
            states = {}
            for n, s in inst.items():
                if n not in self.template_states:
                    raise CannotRestoreStateError(
                        f"partition snapshot has unknown query {n!r} "
                        "(app definition changed?)")
                states[n] = _to_device(s, self.template_states[n])
            self.instances[key] = states
            self.last_seen[key] = now  # restored keys age from restore time


class _PartitionStreamReceiver(Receiver):
    """Reference: core/partition/PartitionStreamReceiver.java:44."""

    def __init__(self, runtime: PartitionRuntime, sid: str) -> None:
        self.runtime = runtime
        self.sid = sid

    def on_batch(self, batch: EventBatch, now: int) -> None:
        self.runtime.route(self.sid, batch, now)


class _GlobalStreamReceiver(Receiver):
    def __init__(self, runtime: PartitionRuntime, sid: str) -> None:
        self.runtime = runtime
        self.sid = sid

    def on_batch(self, batch: EventBatch, now: int) -> None:
        self.runtime.broadcast(self.sid, batch, now)
