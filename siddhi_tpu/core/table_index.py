"""A device index for a table with one `@PrimaryKey` attribute: the key's
slot in an exact 64-bit table (`ops/slot_table.py`, shared with the keyed
partition and the key-and-threshold pattern) IS the row.

Reference: IndexEventHolder.java:60 keeps a primary-key table as a hash map
from the key to its row; a write or a probe by the key is one map access.
Here the map is the slot table and a batch of writes or probes is one device
program:

- **write** (`jit_table_write_<table>`, one a batch): each lane's key finds
  its slot, or takes the next free one (new keys in the order of their first
  lane); the lanes are sorted by (slot, lane), so the events of a key apply in
  arrival order as a per-event evaluation applies them. For
  `update or insert` the last event of a key wins the attributes its `set`
  writes (a new key's row is inserted from its first event, as upstream
  inserts the event that found no row, and updated by the rest); for a plain
  `insert into` the first wins and the others are counted as duplicates. A
  key that finds no free row (capacity used up, or both its buckets full) is
  dropped and counted, never aliased. Rows are written in place (the state is
  donated); nothing is read back per batch: the counters stay on the device
  and are fetched every 64th write and at a report.
- **probe** (inside the join's step program, `ops/join.py` callers): one
  bucket lookup and one row gather a lane, at most one match a lane.

Which tables take the index is decided from the app's text alone
(`index_refusal`), by the runtime and by the lint (SL120) alike; every other
table, and an indexed table's other readers, stay on the columnar `[B, C]`
path of `core/table.py`.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.keyed_window import key_words
from ..ops.lanes import from_words, words
from ..ops.slot_table import (SlotTable, init_slot_table, lookup,
                              lookup_or_insert)
from ..query_api.definition import AttributeType
from ..query_api.execution import OutputAction, Query
from ..query_api.expression import Compare, CompareOp, Variable
from ..telemetry.tracing import stage

#: the device counters, in the order of `TableIndex.counts`
COUNTERS = ("inserted", "updated", "dropped", "duplicates", "probes", "hits")
#: key types the slot table compares whole (a string is its interned code)
KEY_TYPES = (AttributeType.INT, AttributeType.LONG, AttributeType.STRING)
#: writes between two fetches of the counters
SYNC_EVERY = 64


class TableIndex(NamedTuple):
    slots: SlotTable
    counts: jax.Array  # int64[len(COUNTERS)]


def init_index(capacity: int) -> TableIndex:
    return TableIndex(init_slot_table(capacity),
                      jnp.zeros((len(COUNTERS),), jnp.int64))


def bump(counts: jax.Array, **by) -> jax.Array:
    """`counts` with each named counter raised by its int32 scalar."""
    return counts + jnp.stack([
        by[n].astype(jnp.int64) if n in by else jnp.int64(0)
        for n in COUNTERS])


def _writers(app, table_id: str):
    queries = list(app.queries)
    for p in app.partitions:
        queries += [q for q in p.queries if isinstance(q, Query)]
    return [q for q in queries
            if q.output_stream is not None
            and q.output_stream.target_id == table_id]


def inserted_by(app, table_id: str) -> bool:
    """Some query inserts rows into the table (`insert into`, or the
    insert half of `update or insert`)."""
    return any(q.output_stream.action in (OutputAction.INSERT,
                                          OutputAction.UPDATE_OR_INSERT)
               for q in _writers(app, table_id))


def _names_of(query: Query) -> set:
    ins = query.input_stream
    return {None, "__out__", getattr(ins, "stream_id", None),
            getattr(ins, "reference_id", None)} - {""}


def keyed_on(on, table_id: str, pk: str, names) -> bool:
    """`on` is exactly `T.pk == pk` (either way round), the second naming
    the output's own attribute of the key's name."""
    if not (isinstance(on, Compare) and on.op == CompareOp.EQUAL):
        return False
    for t, o in ((on.left, on.right), (on.right, on.left)):
        if (isinstance(t, Variable) and t.stream_id == table_id
                and t.attribute == pk and isinstance(o, Variable)
                and o.stream_id in names and o.attribute == pk):
            return True
    return False


def _reads(expr, table_id: str) -> bool:
    from ..ops.join import collect_vars
    return any(v.stream_id == table_id for v in collect_vars(expr))


def index_refusal(definition, app) -> Optional[str]:
    """None where the table takes the device index; else why it stays on
    the `[B, C]` path. Reads the app's text alone."""
    annotations = definition.annotations or ()
    if any(a.name.lower() == "store" for a in annotations):
        return "it is a @store table (its cache is probed on the [B, C] path)"
    pk = definition.annotation("PrimaryKey") if annotations else None
    if pk is None:
        return "it has no @PrimaryKey"
    if len(pk.elements) != 1:
        return "its @PrimaryKey is composite"
    key = pk.elements[0].value
    types = {a.name: a.type for a in definition.attributes}
    if types.get(key) not in KEY_TYPES:
        return (f"its key {key!r} is {types.get(key)}, not an int, long or "
                "string")
    tid = definition.id
    for q in _writers(app, tid):
        out = q.output_stream
        name = q.name or "a query"
        if out.action == OutputAction.INSERT:
            continue
        if out.action == OutputAction.DELETE:
            return f"{name} deletes from it"
        if out.action == OutputAction.UPDATE:
            return f"{name} updates it without insert"
        if out.action != OutputAction.UPDATE_OR_INSERT:
            return f"{name} writes it by {out.action.name}"
        if not keyed_on(out.on_condition, tid, key, _names_of(q)):
            return (f"{name}'s `on` is not `{tid}.{key} == {key}`")
        for sa in out.set_attributes:
            if sa.table_variable.attribute == key:
                return f"{name} sets the key {key!r}"
            if _reads(sa.expression, tid):
                return f"{name}'s `set` reads the table's own attributes"
    return None


def probe_key(on, table_id: str, table_ref: str, pk: str, resolver,
              probe_ref: str):
    """The expression of the probe side that a join's `on` equates, at its
    top level, to the table's key: `T.pk == <probe expression>`, or None.
    A string constant is no key."""
    from ..ops.join import frames_of, split_conjuncts
    for conj in split_conjuncts(on):
        if not (isinstance(conj, Compare) and conj.op == CompareOp.EQUAL):
            continue
        for t, o in ((conj.left, conj.right), (conj.right, conj.left)):
            if not (isinstance(t, Variable) and t.attribute == pk
                    and frames_of(t, resolver) == {table_ref}):
                continue
            if frames_of(o, resolver) == {probe_ref}:
                return o
    return None


# ----------------------------------------------------------------- programs


def key_pair(col: jax.Array, dtype) -> tuple:
    return key_words(col.astype(dtype))


def probe(index: TableIndex, capacity: int, key: jax.Array, live):
    """(slot[B], hit[B], counts'): each live lane's row (`capacity` where
    its key has none), whether it has one, and the counters with the
    probes and hits added. Books to `table/lookup`."""
    with stage("table"):
        with stage("table/lookup"):
            hi, lo = key_words(key)
            slot = lookup(index.slots, capacity, hi, lo, live)
            hit = live & (slot < capacity)
            counts = bump(index.counts,
                          probes=jnp.sum(live, dtype=jnp.int32),
                          hits=jnp.sum(hit, dtype=jnp.int32))
    return slot, hit, counts


def _scatter(col, at, vals):
    """`col.at[at].set(vals)` for a non-decreasing `at`, an index outside
    the column (negative too) dropped; an 8-byte column as its two 32-bit
    words: the TPU emulates a 64-bit scatter over the whole column (≈ 7.5 ms
    a scatter at 2^20 rows on a TPU v5e, PERF.md), not over the lanes."""
    hints = dict(mode="drop", indices_are_sorted=True,
                 wrap_negative_indices=False)
    if col.dtype.itemsize != 8:
        return col.at[at].set(vals.astype(col.dtype), **hints)
    return from_words([w.at[at].set(v, **hints) for w, v in
                       zip(words(col), words(vals.astype(col.dtype)))],
                      col.dtype)


def _gather_words(tree, idx):
    """Every `[N]` leaf of `tree` at lanes `idx`, through ONE gather of the
    leaves' 32-bit words stacked words-major, `[W, N]` (an 8-byte column as
    its two words): the lanes stay the minor axis, where a `[N, W]` block
    pads each row to 128 words on the TPU."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    split = [words(a) for a in leaves]
    mat = jnp.stack([w for ws in split for w in ws])[:, idx]
    out, at = [], 0
    for a, ws in zip(leaves, split):
        out.append(from_words(list(mat[at:at + len(ws)]), a.dtype))
        at += len(ws)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_write(attr_dtypes: dict, key: str, capacity: int, upsert: bool):
    """The body of one table-write program:
    `(cols, ts, valid, index, out_cols, out_ts, live, set_vals) -> (cols,
    ts, valid, index)`. `set_vals` holds, per attribute an update writes,
    its value on every lane (upsert only); an inserted row takes the
    output's attribute of its name.

    The batch's rows move packed (`_gather_words`: an 8-byte column as its
    two words, every column of one index in one gather): once into slot
    order, then each run's first row (what an insert takes) and its last
    (what an update sets): three gathers a batch in place of a gather a
    column. Words-major, as `[W, B]`: a `[B, W]` block (`ops/lanes.py`
    `gather_lanes`) took 4.3 ms at 131,072 lanes on a TPU v5e, `[W, B]`
    1.07 (PERF.md).
    Every scatter into the table's columns runs in slot order and says so:
    the TPU sorts a scatter's indices first where it is not told."""
    C = capacity

    def write(cols, ts, valid, index, out_cols, out_ts, live, set_vals):
        B = live.shape[0]
        with stage("table"):
            with stage("table/lookup"):
                hi, lo = key_pair(out_cols[key], attr_dtypes[key])
                n0 = index.slots.count
                slots, slot = lookup_or_insert(index.slots, C, hi, lo, live)
            with stage("table/order"):
                # the events of a key side by side, in arrival order; lanes
                # with no row (invalid, or no free row) sort last
                s_slot, s_lane = lax.sort(
                    (slot, lax.iota(jnp.int32, B)), num_keys=2)
                edge = s_slot[1:] != s_slot[:-1]
                start = jnp.concatenate([jnp.ones((1,), bool), edge])
                end = jnp.concatenate([edge, jnp.ones((1,), bool)])
                # each position's run start and end
                pos = lax.iota(jnp.int32, B)
                first = lax.cummax(jnp.where(start, pos, 0))
                last = lax.cummin(jnp.where(end, pos, B - 1), reverse=True)
                placed = s_slot < C
                new = placed & (s_slot >= n0)
                sets = set_vals if upsert else {}
                s_out, s_ts, s_set = _gather_words(
                    ({a: out_cols[a] for a in attr_dtypes}, out_ts, sets),
                    s_lane)
            with stage("table/write"):
                # every lane of a run writes its run's value to the run's
                # row: duplicate indices with one value, in slot order
                ins, ins_ts = _gather_words(
                    ({a: s_out[a] for a in attr_dtypes if a not in sets},
                     s_ts), first)
                upd = _gather_words(s_set, last) if sets else {}
                # rows taken before this batch fall off the front: the
                # indices stay non-decreasing
                at_new = jnp.where(s_slot < n0, -1, s_slot)
                # a new key's lone event is its insert
                alone = new & (first == last)
                new_cols = dict(cols)
                for a, dt in attr_dtypes.items():
                    if a in sets:
                        new_cols[a] = _scatter(
                            cols[a], s_slot,
                            jnp.where(alone, s_out[a].astype(dt),
                                      upd[a].astype(dt)))
                    else:
                        new_cols[a] = _scatter(cols[a], at_new, ins[a])
                new_ts = _scatter(ts, at_new, ins_ts)
                new_valid = _scatter(valid, at_new, jnp.ones((B,), bool))
                n_placed = jnp.sum(placed, dtype=jnp.int32)
                n_new = jnp.sum(new & start, dtype=jnp.int32)
                counts = bump(
                    index.counts, inserted=n_new,
                    dropped=jnp.sum(live & (slot >= C), dtype=jnp.int32),
                    **{("updated" if upsert else "duplicates"):
                       n_placed - n_new})
        return new_cols, new_ts, new_valid, TableIndex(slots, counts)

    return write


def make_rebuild(attr_dtypes: dict, key: str, capacity: int):
    """`(cols, ts, valid, counts) -> (cols, ts, valid, index)`: the rows
    moved to the front in their order and the index made anew over them
    (after a write the index did not see: a restore, an on-demand query)."""
    C = capacity

    def rebuild(cols, ts, valid, counts):
        order = jnp.argsort(~valid, stable=True)
        cols = {a: v[order] for a, v in cols.items()}
        ts, valid = ts[order], valid[order]
        hi, lo = key_pair(cols[key], attr_dtypes[key])
        slots, _ = lookup_or_insert(init_slot_table(C), C, hi, lo, valid)
        return cols, ts, valid, TableIndex(slots, counts)

    return rebuild
