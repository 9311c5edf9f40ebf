"""Tables: CRUD event stores queryable from streams.

Reference: core/table/InMemoryTable.java:58 (rows under a RW lock, CRUD via
CompiledCondition built by OperatorParser; index planning via
core/table/holder/IndexEventHolder + the CollectionExecutor mini-optimizer).

TPU-native design: a table is a **columnar device store** — capacity-padded
arrays + validity mask held as a pytree (`TableState`) so table contents can be
passed *into* jitted query steps as arguments (contents change between
batches; they must never be baked into a trace as constants). CRUD is
vectorized:

- conditions compile once into broadcastable column functions: stream frames
  enter the scope as [B,1] columns, the table frame as [C] columns, so any
  mixed condition evaluates to a [B,C] cross mask — the TPU analogue of the
  reference's per-event `Operator.find` walks;
- delete = any-over-B of the mask clears row validity;
- update = last-matching-event-wins gather (the reference applies events
  sequentially; per-row multi-event read-modify-write chains are the one
  divergence, documented in tests);
- insert/update-or-insert scatter into free slots computed by stable argsort
  of the validity mask; an update-or-insert's events that no row matched
  insert in arrival order (a later one that matches an earlier one's new row
  updates it).

The reference's primary-key/index holders become: a single-attribute primary
key whose writers the device index can take is that index
(core/table_index.py: one program a write batch, one lookup a join probe, no
[B,C] mask); any other primary key is a compiled key-equality condition, its
inserts deduplicated by [B,C] and [B,B] masks. Duplicate primary-key inserts
are dropped (reference throws; we surface a counter).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import CapacityExceededError, SiddhiAppCreationError
from ..ops.search import stable_partition_order
from ..query_api.definition import AttributeType, TableDefinition
from ..query_api.execution import OutputAction, OutputStream, UpdateSetAttribute
from ..query_api.expression import Compare, CompareOp, Expression, Variable
from . import dtypes
from .context import SiddhiAppContext
from .event import EventBatch, EventType, StreamCodec


def refuse_masks(what: str, nbytes: int) -> None:
    """Refuse at build a columnar-path program whose cross masks exceed the
    device's memory (it would fail at its first batch)."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = int(stats.get("bytes_limit", 16 << 30))
    if nbytes > limit:
        raise SiddhiAppCreationError(
            f"{what}: its [B, C] masks take {nbytes:,} bytes a batch, more "
            f"than the device's {limit:,}; a single-attribute @PrimaryKey "
            "written by `insert into` or `update or insert ... on "
            "T.key == key` takes the device index instead (SL120)")


class TableState(NamedTuple):
    """Device-resident table contents (a pytree; jit-argument friendly)."""

    cols: dict
    ts: jax.Array  # int64[C]
    valid: jax.Array  # bool[C]


def _broadcast_scope(scope, table_id: str, tstate: TableState):
    """Clone a [B]-shaped scope into a [B,1]-shaped one and add the [C] table
    frame, so compiled conditions evaluate to [B,C] cross masks."""
    from ..ops.expr_compile import Scope

    s2 = Scope()
    for ref, cols in scope.frames.items():
        s2.add_frame(
            ref,
            {k: v[:, None] for k, v in cols.items()},
            scope.ts[ref][:, None],
            scope.valids[ref][:, None],
            default=(ref == scope.default_frame),
        )
    s2.add_frame(table_id, tstate.cols, tstate.ts, tstate.valid)
    s2.extras = dict(scope.extras)
    return s2


class InMemoryTable:
    """Host handle owning the device TableState + compiled per-query ops."""

    def __init__(self, definition: TableDefinition, ctx: SiddhiAppContext,
                 capacity: Optional[int] = None,
                 indexed: bool = False) -> None:
        self.definition = definition
        self.ctx = ctx
        self.codec = StreamCodec(definition, ctx.global_strings)
        self.capacity = capacity or dtypes.stated_capacity(
            definition.annotations).rows \
            or dtypes.config.default_table_capacity
        self.attr_types = {a.name: a.type for a in definition.attributes
                          if a.type != AttributeType.OBJECT}
        self._state = TableState(
            cols={n: jnp.zeros((self.capacity,), dtypes.device_dtype(t))
                  for n, t in self.attr_types.items()},
            ts=jnp.zeros((self.capacity,), dtypes.TS_DTYPE),
            valid=jnp.zeros((self.capacity,), jnp.bool_),
        )
        # @PrimaryKey('a' [, 'b']) — reference: EventHolderPasser.java reads it
        # to pick an IndexEventHolder.
        pk = definition.annotation("PrimaryKey") if definition.annotations else None
        self.primary_keys: tuple[str, ...] = tuple(
            e.value for e in pk.elements) if pk is not None else ()
        # @Index('a' [, 'b']) — reference: IndexEventHolder.java:60 secondary
        # TreeMap indexes. TPU form: a sorted copy of each indexed column
        # (invalid rows sort to the end as dtype-max sentinels) rebuilt
        # lazily after mutations; equality probes binary-search it instead
        # of scanning the [B, C] cross mask.
        idx_ann = definition.annotation("Index") if definition.annotations else None
        self.index_attrs: tuple[str, ...] = tuple(
            e.value for e in idx_ann.elements) if idx_ann is not None else ()
        for a in self.index_attrs:
            if a not in self.attr_types:
                raise SiddhiAppCreationError(
                    f"@Index({a!r}): no such attribute on {definition.id!r}")
            if self.attr_types[a] == AttributeType.BOOL:
                raise SiddhiAppCreationError(
                    f"@Index({a!r}): bool attributes are not indexable")
        self._indexes = None  # dict[attr, (sorted_vals[C], n_live)] | None
        self._index_fn = None
        self._host_duplicates = 0
        # the device index of a single-attribute primary key
        # (core/table_index.py), where the app's text lets it take every
        # write: the key's slot is the row
        self.index_key: Optional[str] = None
        self._index = None
        if indexed:
            from . import table_index
            from ..telemetry.tracing import StageCells
            (self.index_key,) = self.primary_keys
            self._index = table_index.init_index(self.capacity)
            self._index_stale = False
            self._insert_writer = None
            self._rebuild_fn = None
            self.steps = 0
            self.synced = {n: 0 for n in ("rows",) + table_index.COUNTERS}
            self._drop_warned = False
            # statistics_report()["tables"][id]["stage_ms"]
            self.cells = StageCells(("write", "drop_sync"))
        self._insert_fn = jax.jit(self._make_insert())

    # ------------------------------------------------------------------ state

    @property
    def state(self) -> TableState:
        return self._state

    @state.setter
    def state(self, new_state: TableState) -> None:
        self._state = new_state
        self._indexes = None  # any mutation invalidates the sorted copies
        if self.index_key is not None:
            # written past the index (a restore, an on-demand query): made
            # anew before its next use
            self._index_stale = True

    def clear(self) -> None:
        """Reset to empty, keeping compiled kernels and capacity."""
        self.state = TableState(
            cols={k: jnp.zeros_like(v) for k, v in self._state.cols.items()},
            ts=jnp.zeros_like(self._state.ts),
            valid=jnp.zeros_like(self._state.valid),
        )

    # ------------------------------------------------------------ the index

    @property
    def dropped_duplicates(self) -> int:
        """Inserts of a key the table held, dropped (upstream throws)."""
        if self.index_key is not None:
            self.sync_counters(jax.device_get(self.device_counters()))
            return self.synced["duplicates"]
        return self._host_duplicates

    def index_state(self):
        """The device index, made anew first where a write went past it."""
        if self._index_stale:
            from . import table_index
            if self._rebuild_fn is None:
                self._rebuild_fn = jax.jit(table_index.make_rebuild(
                    self._dtypes(), self.index_key, self.capacity))
            s = self._state
            cols, ts, valid, self._index = self._rebuild_fn(
                s.cols, s.ts, s.valid, self._index.counts)
            self._state = TableState(cols, ts, valid)
            self._index_stale = False
        return self._index

    def _dtypes(self) -> dict:
        return {a: v.dtype for a, v in self._state.cols.items()}

    def insert_writer(self):
        """The table-write program (`jit_table_write_<table>`) of a plain
        `insert into`: `(cols, ts, valid, index, out_cols, out_ts, live,
        set_vals) -> (cols, ts, valid, index)`, the table's state donated."""
        if self._insert_writer is None:
            from . import table_index
            from .join_runtime import _named
            body = table_index.make_write(self._dtypes(), self.index_key,
                                          self.capacity, False)
            self._insert_writer = jax.jit(
                _named(body, f"table_write_{self.definition.id}"),
                donate_argnums=(0, 1, 2, 3))
        return self._insert_writer

    def write_indexed(self, fn, *args) -> None:
        """One table write through the index: a device program, no sync
        (the counters are fetched every SYNC_EVERY-th write)."""
        from .table_index import SYNC_EVERY
        index = self.index_state()
        s = self._state
        with self.cells.span("write", "siddhi.table.write",
                             table=self.definition.id):
            cols, ts, valid, self._index = fn(s.cols, s.ts, s.valid, index,
                                              *args)
        self._state = TableState(cols, ts, valid)
        self._indexes = None
        self.steps += 1
        if not self._drop_warned and self.steps % SYNC_EVERY == 0:
            # a device sync under the controller lock, as the join's: it
            # waits for every program dispatched so far
            with self.cells.span("drop_sync", "siddhi.table.drop_sync",
                                 table=self.definition.id):
                self.sync_counters(jax.device_get(self.device_counters()))

    def set_counts(self, counts) -> None:
        """The counters a join's probe program returned."""
        self._index = self._index._replace(counts=counts)

    def device_counters(self) -> dict:
        """Copies of the index's counters for one fetch (under the
        controller lock: the next write donates them)."""
        index = self.index_state()
        return {"rows": jnp.copy(index.slots.count),
                "counts": jnp.copy(index.counts)}

    def sync_counters(self, fetched: dict) -> None:
        from .table_index import COUNTERS
        self.synced["rows"] = int(fetched["rows"])
        self.synced.update(
            {n: int(v) for n, v in zip(COUNTERS, fetched["counts"])})
        if self.synced["dropped"] and not self._drop_warned:
            import warnings
            warnings.warn(
                f"table {self.definition.id!r}: {self.synced['dropped']} "
                f"writes of keys beyond its {self.capacity} rows were "
                "dropped — raise @capacity(rows=...) on the table",
                stacklevel=2)
            self._drop_warned = True

    def stats_snapshot(self) -> dict:
        """statistics_report()["tables"][id]. `rows` (rows taken) and the
        device counters are as last synced: at a report and every 64th
        write; `steps` (writes) is the host's count."""
        return {"capacity": self.capacity, "key": self.index_key,
                "steps": self.steps, **self.synced,
                "stage_ms": self.cells.snapshot()}

    def probe_indexes(self) -> dict:
        """Sorted-copy indexes for in-kernel equality probes; rebuilt lazily
        (one jitted sort per indexed column) after mutations."""
        indexable = tuple(sorted(self.indexable_eq_attrs()))
        if not indexable:
            return {}
        if self._indexes is None:
            if self._index_fn is None:
                attrs = indexable

                def build(tstate: TableState):
                    out = {}
                    n_live = jnp.sum(tstate.valid, dtype=jnp.int32)
                    for a in attrs:
                        col = tstate.cols[a]
                        if jnp.issubdtype(col.dtype, jnp.floating):
                            big = jnp.asarray(jnp.inf, col.dtype)
                        else:
                            big = jnp.asarray(jnp.iinfo(col.dtype).max,
                                              col.dtype)
                        keys = jnp.where(tstate.valid, col, big)
                        out[a] = (jnp.sort(keys), n_live)
                    return out

                self._index_fn = jax.jit(build)
            self._indexes = self._index_fn(self._state)
        return self._indexes

    # ------------------------------------------------------------------ insert

    def _make_insert(self):
        pk = self.primary_keys

        def insert(tstate: TableState, batch: EventBatch):
            C = tstate.ts.shape[0]
            B = batch.ts.shape[0]
            ins = batch.valid
            if pk:
                # drop rows whose primary key already exists (reference throws
                # PrimaryKeyViolationException; we drop + count host-side)
                eq = jnp.ones((B, C), bool)
                for k in pk:
                    eq = eq & (batch.cols[k][:, None] == tstate.cols[k][None, :])
                dup = (eq & tstate.valid[None, :]).any(axis=1)
                # also dedupe within the batch: keep first occurrence
                eq_b = jnp.ones((B, B), bool)
                for k in pk:
                    eq_b = eq_b & (batch.cols[k][:, None] == batch.cols[k][None, :])
                earlier = jnp.tril(jnp.ones((B, B), bool), k=-1)
                dup_in_batch = (eq_b & earlier & ins[None, :]).any(axis=1)
                ins = ins & ~dup & ~dup_in_batch
            n_ins = jnp.sum(ins.astype(jnp.int32))
            # free slots in row order: argsort(valid) puts False (free) first
            free_order = stable_partition_order(~tstate.valid)
            n_free = jnp.sum((~tstate.valid).astype(jnp.int32))
            rank = jnp.cumsum(ins.astype(jnp.int32)) - 1
            fits = ins & (rank < n_free)
            slot = jnp.where(fits, free_order[jnp.clip(rank, 0, C - 1)], C)
            new_cols = {k: v.at[slot].set(batch.cols[k], mode="drop")
                        for k, v in tstate.cols.items()}
            new_ts = tstate.ts.at[slot].set(batch.ts, mode="drop")
            new_valid = tstate.valid.at[slot].set(True, mode="drop")
            overflow = n_ins - jnp.sum(fits.astype(jnp.int32))
            dropped = jnp.sum((batch.valid & ~ins).astype(jnp.int32))
            return TableState(new_cols, new_ts, new_valid), overflow, dropped

        return insert

    def insert_batch(self, batch: EventBatch) -> None:
        if self.index_key is not None:
            # through the index: the first event of a key wins, the others
            # are counted as duplicates; a key with no free row is dropped
            # and counted
            self.write_indexed(self.insert_writer(), batch.cols,
                               batch.ts, batch.valid, {})
            return
        new_state, overflow, dropped = self._insert_fn(self.state, batch)
        ov = int(overflow)
        if ov:
            # all-or-nothing: leave self.state untouched on overflow
            raise CapacityExceededError(
                f"table {self.definition.id} capacity {self.capacity} exceeded "
                f"({ov} rows would be dropped)")
        self.state = new_state
        self._host_duplicates += int(dropped)

    def insert_rows(self, rows, timestamp: int = 0) -> None:
        cols = self.codec.rows_to_columns(rows, n_pad=len(rows))
        ts = np.full(len(rows), timestamp, dtype=np.int64)
        self.insert_batch(EventBatch.from_numpy(ts, cols, len(rows)))

    # ------------------------------------------------------------------ reads

    def find_mask(self, cond: Optional[Callable], scope) -> jax.Array:
        """[B,C] cross mask of (stream event, table row) matches. `cond` is a
        compiled condition; None matches every valid row."""
        s2 = _broadcast_scope(scope, self.definition.id, self.state)
        B = next(iter(scope.valids.values())).shape[0]
        m = jnp.ones((B, self.capacity), bool) if cond is None else \
            jnp.broadcast_to(cond(s2), (B, self.capacity))
        return m & self.state.valid[None, :]

    def contains_probe(self, scope, inner, eq_plan=None) -> jax.Array:
        """`expr in Table` membership (reference: InConditionExpressionExecutor):
        any-match over table rows per stream lane. Reads the table state from
        scope.extras so jitted steps see fresh contents each call.

        When the condition is a single equality on an @Index'd (or sole
        primary-key) attribute, `eq_plan` carries (attr, stream_expr) and the
        probe binary-searches the sorted index — O(B log C) instead of the
        [B, C] cross mask (reference: IndexEventHolder index-aware plans)."""
        tid = self.definition.id
        if eq_plan is not None:
            attr, sexpr = eq_plan
            idx = scope.extras.get(f"tableidx:{tid}")
            if idx and attr in idx:
                from ..ops.search import searchsorted32
                sorted_vals, n_live = idx[attr]
                C = sorted_vals.shape[0]
                v = sexpr(scope).astype(sorted_vals.dtype)
                pos = searchsorted32(sorted_vals, v, side="left")
                return (pos < n_live) & \
                    (sorted_vals[jnp.clip(pos, 0, C - 1)] == v)
        tstate: TableState = scope.extras.get(f"table:{tid}", self.state)
        s2 = _broadcast_scope(scope, tid, tstate)
        if inner is None:
            raise SiddhiAppCreationError("`in Table` requires a condition")
        m = inner(s2) & tstate.valid
        return m.any(axis=-1)

    def indexable_eq_attrs(self) -> set:
        """Attributes whose equality probes can use a sorted index."""
        out = set(self.index_attrs)
        if len(self.primary_keys) == 1:
            out.add(self.primary_keys[0])
        return out

    def all_rows(self) -> list[tuple]:
        if self.index_key is not None:
            self.index_state()
        batch = EventBatch(ts=self.state.ts, cols=self.state.cols,
                           valid=self.state.valid,
                           types=jnp.zeros((self.capacity,), jnp.int8))
        return [e.data for e in batch.to_host_events(self.codec)]

    def __len__(self) -> int:
        return int(jnp.sum(self.state.valid))


class TableOutputExecutor:
    """Compiled runtime for one query output targeting a table — the analogue
    of the reference's {Delete,Update,UpdateOrInsert}TableCallback +
    OperatorParser-compiled Operator.

    Built once per query at plan time; executes as one jitted device function
    `(table_state, out_batch) -> table_state'`.
    """

    def __init__(self, table: InMemoryTable, output_stream: OutputStream,
                 out_types: dict[str, AttributeType],
                 out_codec: StreamCodec, registry,
                 out_frame_aliases: Sequence[str] = ()) -> None:
        from ..ops.expr_compile import Scope, TypeResolver, compile_expression

        self.table = table
        self.action = output_stream.action
        tid = table.definition.id

        # resolver over {output-stream frame} + {table frame}; the ON condition
        # may reference output attrs via the query's input-stream name
        # (reference: the matching meta carries the stream alias)
        frames = {"__out__": dict(out_types), tid: dict(table.attr_types)}
        codecs = {"__out__": out_codec, tid: table.codec}
        for alias in out_frame_aliases:
            if alias and alias not in frames:
                frames[alias] = dict(out_types)
                codecs[alias] = out_codec
        self.out_frame_aliases = tuple(
            a for a in out_frame_aliases if a and a != tid)
        resolver = TypeResolver(frames, "__out__", codecs)

        cond = output_stream.on_condition
        if cond is None:
            raise SiddhiAppCreationError(
                f"{self.action.name} into table requires an ON condition")
        self.cond = compile_expression(cond, resolver, registry)
        if self.cond.type != AttributeType.BOOL:
            raise SiddhiAppCreationError("table ON condition must be boolean")

        # SET clause (update/update-or-insert); default: set every table attr
        # from the same-named output attr (reference: UpdateSet defaults)
        sets: list[tuple[str, object]] = []
        if output_stream.set_attributes:
            for sa in output_stream.set_attributes:
                if sa.table_variable.stream_id not in (None, tid):
                    raise SiddhiAppCreationError(
                        f"SET target must be a {tid} attribute")
                sets.append((sa.table_variable.attribute,
                             compile_expression(sa.expression, resolver, registry)))
        else:
            for name, t in table.attr_types.items():
                if name in out_types:
                    sets.append((name, compile_expression(
                        Variable(name, stream_id="__out__"), resolver, registry)))
        self.sets = sets

        self._fn = jax.jit(self._make())

    def mask_bytes(self, batch: int) -> int:
        """Bytes of the `[B, C]` masks one batch of `batch` lanes builds:
        the condition's, and one per `set` value."""
        per = 1 + sum(jnp.dtype(dtypes.device_dtype(ce.type)).itemsize
                      for _, ce in self.sets)
        rows = self.table.capacity
        if self.action == OutputAction.UPDATE_OR_INSERT:
            rows += batch  # the batch's own new rows, in arrival order
        return batch * rows * per

    def _make(self):
        from ..ops.expr_compile import Scope

        table = self.table
        tid = table.definition.id
        action = self.action
        cond = self.cond
        sets = self.sets

        aliases = self.out_frame_aliases

        def run(tstate: TableState, out: EventBatch):
            B = out.ts.shape[0]
            C = tstate.ts.shape[0]
            scope = Scope()
            scope.add_frame("__out__", out.cols, out.ts, out.valid, default=True)
            for alias in aliases:
                scope.add_frame(alias, out.cols, out.ts, out.valid)
            s2 = _broadcast_scope(scope, tid, tstate)
            mask = jnp.broadcast_to(cond(s2), (B, C))
            mask = mask & out.valid[:, None] & tstate.valid[None, :]

            if action == OutputAction.DELETE:
                hit = mask.any(axis=0)
                return TableState(tstate.cols, tstate.ts, tstate.valid & ~hit), \
                    jnp.int32(0)

            # update: last matching event wins per row
            has = mask.any(axis=0)
            b_star = (B - 1) - jnp.argmax(mask[::-1, :], axis=0)  # [C]
            new_cols = dict(tstate.cols)
            rows = jnp.arange(C)
            for name, ce in sets:
                vals = jnp.broadcast_to(ce(s2), (B, C))  # [B,C]
                picked = vals[b_star, rows].astype(tstate.cols[name].dtype)
                new_cols[name] = jnp.where(has, picked, tstate.cols[name])
            updated = TableState(new_cols, tstate.ts, tstate.valid)

            if action == OutputAction.UPDATE:
                return updated, jnp.int32(0)

            # update-or-insert: events matching no row are inserted, in
            # arrival order as a per-event evaluation inserts them: a later
            # such event whose condition holds against an earlier one's new
            # row updates that row instead (the last one's `set` wins)
            ins = out.valid & ~mask.any(axis=1)
            lanes = jnp.arange(B)
            rows = TableState(
                {k: out.cols[k] if k in out.cols else jnp.zeros((B,), v.dtype)
                 for k, v in tstate.cols.items()}, out.ts, ins)
            s3 = _broadcast_scope(scope, tid, rows)
            found = jnp.broadcast_to(cond(s3), (B, B)) & ins[:, None] \
                & ins[None, :] & (lanes[None, :] < lanes[:, None])
            lead = ins & ~found.any(axis=1)
            has = found.any(axis=0)
            b_star = (B - 1) - jnp.argmax(found[::-1, :], axis=0)
            cols = dict(out.cols)
            for name, ce in sets:
                vals = jnp.broadcast_to(ce(s3), (B, B))
                picked = vals[b_star, lanes].astype(tstate.cols[name].dtype)
                cols[name] = jnp.where(has, picked, rows.cols[name])
            return updated, dataclasses.replace(out, cols=cols, valid=lead)

        return run

    def apply(self, out: EventBatch) -> None:
        if self.action == OutputAction.UPDATE_OR_INSERT:
            new_state, to_insert = self._fn(self.table.state, out)
            self.table.state = new_state
            self.table.insert_batch(to_insert)
        else:
            self.table.state, _ = self._fn(self.table.state, out)


class IndexedTableWriter:
    """`update or insert into T ... on T.key == key` on a table with the
    device index (core/table_index.py): one program a batch
    (`jit_table_write_<table>`), the output's event-type selection inside
    it, no `[B, C]` mask and no sync. Within a batch the events of a key
    apply in arrival order: the last wins what `set` writes."""

    #: QueryRuntime._distribute hands the raw output and its event type
    takes_output = True

    def __init__(self, table: InMemoryTable, output_stream: OutputStream,
                 out_types: dict[str, AttributeType],
                 out_codec: StreamCodec, registry,
                 out_frame_aliases: Sequence[str] = ()) -> None:
        from ..ops.expr_compile import TypeResolver, compile_expression

        self.table = table
        tid = table.definition.id
        missing = [a for a in table.attr_types if a not in out_types]
        if missing:
            raise SiddhiAppCreationError(
                f"update or insert into {tid}: the output has no "
                f"{missing} for the row it inserts")
        frames = {"__out__": dict(out_types)}
        codecs = {"__out__": out_codec}
        self.aliases = tuple(a for a in out_frame_aliases
                             if a and a not in (tid, "__out__"))
        for alias in self.aliases:
            frames[alias] = dict(out_types)
            codecs[alias] = out_codec
        resolver = TypeResolver(frames, "__out__", codecs)
        key = table.index_key
        if output_stream.set_attributes:
            self.sets = [(sa.table_variable.attribute,
                          compile_expression(sa.expression, resolver,
                                             registry))
                         for sa in output_stream.set_attributes]
        else:
            # upstream's default: every table attribute from the output's
            # of its name; the key equals the row's by the condition
            self.sets = [(a, compile_expression(
                Variable(a, stream_id="__out__"), resolver, registry))
                for a in table.attr_types if a != key]
        self._fns: dict = {}

    def _program(self, etype):
        fn = self._fns.get(etype)
        if fn is None:
            from ..ops.expr_compile import Scope
            from . import table_index
            from .query_runtime import QueryRuntime
            t = self.table
            write = table_index.make_write(t._dtypes(), t.index_key,
                                           t.capacity, True)
            sets, aliases = self.sets, self.aliases

            def program(cols, ts, valid, index, out):
                out = QueryRuntime._select_event_type(out, etype)
                scope = Scope()
                scope.add_frame("__out__", out.cols, out.ts, out.valid,
                                default=True)
                for alias in aliases:
                    scope.add_frame(alias, out.cols, out.ts, out.valid)
                set_vals = {a: jnp.broadcast_to(ce(scope), out.ts.shape)
                            for a, ce in sets}
                return write(cols, ts, valid, index, out.cols, out.ts,
                             out.valid, set_vals)

            program.__name__ = program.__qualname__ = \
                f"table_write_{self.table.definition.id}"
            fn = self._fns[etype] = jax.jit(program,
                                            donate_argnums=(0, 1, 2, 3))
        return fn

    def apply_output(self, out: EventBatch, etype) -> None:
        self.table.write_indexed(self._program(etype), out)

    def apply(self, out: EventBatch) -> None:
        from ..query_api.execution import OutputEventType
        self.apply_output(out, OutputEventType.CURRENT)

    def warm(self, out: EventBatch, etype) -> None:
        """Compile the program for `out`'s shapes without running it."""
        from .query_runtime import aot_warm
        s = self.table.state
        aot_warm(self._program(etype), s.cols, s.ts, s.valid,
                 self.table.index_state(), out)
