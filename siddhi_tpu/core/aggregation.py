"""Incremental aggregation — `define aggregation A from S select ... group by
... aggregate [by tsAttr] every sec ... year`.

Reference: core/aggregation/ — AggregationRuntime.java:82 (per-duration
executor chain + tables), IncrementalExecutor.java:50,111 (bucket state,
rollover dispatch into the next-coarser duration),
OutOfOrderEventsDataAggregator (late-event merge),
IncrementalExecutorsInitialiser (restart rebuild), and the incremental
aggregator SPI under core/query/selector/attribute/aggregator/incremental/
(avg decomposes into sum+count, etc.).

TPU re-design — no cascade, no rollover events: because `bucket_start(d, ts)`
is a pure function of the event timestamp, each micro-batch scatters directly
into EVERY duration's bucket store (6 fused scatter-adds per batch instead of
an event-at-a-time rollover chain). Consequences, all deliberate:
  * out-of-order events need no special path — a late event's bucket is
    derived from its own timestamp and the scatter-add is order-invariant
    (replaces OutOfOrderEventsDataAggregator);
  * restart needs no rebuild — the stores ARE the persistent state, snapshot
    like every other pytree (replaces IncrementalExecutorsInitialiser);
  * `within ... per ...` reads are a mask over one duration's store, not a
    multi-table merge (replaces IncrementalAggregateCompileCondition).
Month/year buckets use Hinnant civil-calendar integer arithmetic on device
(GMT, matching the reference's default timezone —
core/util/IncrementalTimeConverterUtil.java).
"""

from __future__ import annotations

import dataclasses
from datetime import datetime, timezone
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..errors import DefinitionNotExistError, SiddhiAppCreationError
from ..extension.registry import ExtensionKind, Registry
from ..ops.aggregators import AggregatorFactory, AggregatorSpec
from ..ops.expr_compile import Scope, TypeResolver, compile_expression
from ..ops.groupby import KeyTable, hash_columns, init_key_table, key_lookup_or_insert
from ..query_api.definition import (
    AggregationDefinition,
    Attribute,
    AttributeType,
    Duration,
    StreamDefinition,
)
from ..query_api.expression import AttributeFunction, Constant, Expression, Variable
from . import dtypes
from .context import SiddhiAppContext
from .event import EventBatch, StreamCodec
from .stream import Receiver

AGG_TIMESTAMP = "AGG_TIMESTAMP"

_MS_WIDTH = {
    Duration.SECONDS: 1_000,
    Duration.MINUTES: 60_000,
    Duration.HOURS: 3_600_000,
    Duration.DAYS: 86_400_000,
}

_DAY_MS = 86_400_000


def _civil_from_days(days):
    """Hinnant civil_from_days: epoch day count → (year, month). Pure int64
    arithmetic, vectorized (GMT)."""
    z = days + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(doe - doe // 1460 + doe // 36524 - doe // 146096, 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = jnp.floor_divide(5 * doy + 2, 153)
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    doy = jnp.floor_divide(153 * (m + jnp.where(m > 2, -3, 9)) + 2, 5) + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def bucket_start(duration: Duration, ts):
    """Bucket start (epoch ms) containing each timestamp, per duration.
    Reference: IncrementalTimeConverterUtil.getStartTimeOfAggregates."""
    ts = ts.astype(jnp.int64)
    if duration in _MS_WIDTH:
        w = _MS_WIDTH[duration]
        return ts - jnp.remainder(ts, w)
    days = jnp.floor_divide(ts, _DAY_MS)
    y, m = _civil_from_days(days)
    if duration == Duration.MONTHS:
        return _days_from_civil(y, m, jnp.ones_like(m)) * _DAY_MS
    if duration == Duration.YEARS:
        return _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y)) * _DAY_MS
    raise SiddhiAppCreationError(f"unsupported duration {duration}")


def bucket_bounds(duration: Duration, t: int) -> tuple[int, int]:
    """[start, end) of the bucket containing instant `t` (host scalars)."""
    lo = int(bucket_start(duration, jnp.array([t], jnp.int64))[0])
    if duration in _MS_WIDTH:
        return lo, lo + _MS_WIDTH[duration]
    probe = lo + (40 if duration == Duration.MONTHS else 370) * _DAY_MS
    hi = int(bucket_start(duration, jnp.array([probe], jnp.int64))[0])
    return lo, hi


def parse_time_constant(value) -> int:
    """`within` bound → epoch ms. Accepts epoch millis (int) or the
    reference's datetime string formats `yyyy-MM-dd HH:mm:ss` (GMT) with
    optional `+HH:MM` offset (reference: AggregationParser within handling)."""
    if isinstance(value, int):
        return value
    if isinstance(value, str):
        s = value.strip()
        for fmt in ("%Y-%m-%d %H:%M:%S %z", "%Y-%m-%d %H:%M:%S"):
            try:
                dt = datetime.strptime(s, fmt)
                if dt.tzinfo is None:
                    dt = dt.replace(tzinfo=timezone.utc)
                return int(dt.timestamp() * 1000)
            except ValueError:
                continue
        raise SiddhiAppCreationError(
            f"cannot parse within bound {value!r} (epoch ms or "
            "'yyyy-MM-dd HH:mm:ss [+HH:MM]')")
    raise SiddhiAppCreationError(f"bad within bound {value!r}")


class DurationStore(NamedTuple):
    """One duration's bucket table: composite (bucket, group-key) → dense slot.

    Replaces the reference's per-duration in-memory BaseIncrementalValueStore +
    backing table pair with one device hash table."""

    key_table: KeyTable
    bucket_ts: jax.Array  # int64[K] bucket start per slot
    group_cols: dict  # name -> [K] group attribute value per slot
    comps: tuple  # per flattened component: [K] accumulator
    alive: jax.Array  # bool[K] (False = never used or purged)


@dataclasses.dataclass
class _OutputSpec:
    """One select item of the aggregation definition."""

    name: str
    type: AttributeType
    is_group: bool = False
    group_attr: Optional[str] = None
    spec: Optional[AggregatorSpec] = None
    comp_offset: int = 0  # index of first component in the flat comp list


class AggregationRuntime(Receiver):
    """Runtime for one `define aggregation` (reference:
    core/aggregation/AggregationRuntime.java:82)."""

    def __init__(self, definition: AggregationDefinition, ctx: SiddhiAppContext,
                 input_junction, registry: Registry) -> None:
        self.definition = definition
        self.ctx = ctx
        self.junction = input_junction
        self.durations = tuple(definition.durations)
        if not self.durations:
            raise SiddhiAppCreationError(
                f"aggregation {definition.id!r} needs `aggregate every ...`")

        in_def: StreamDefinition = input_junction.definition
        self.codec_in = input_junction.codec
        attr_types = {a.name: a.type for a in in_def.attributes
                      if a.type != AttributeType.OBJECT}
        frames = {in_def.id: attr_types}
        self.resolver = TypeResolver(frames, in_def.id,
                                     {in_def.id: self.codec_in})
        self.frame_ref = in_def.id

        # --- aggregate by <attr> ---
        self.ts_attr = definition.aggregate_attribute
        if self.ts_attr is not None and attr_types.get(self.ts_attr) != AttributeType.LONG:
            raise SiddhiAppCreationError(
                f"aggregate by {self.ts_attr!r}: attribute must be long epoch ms")

        # --- group-by ---
        self.group_attrs: list[str] = []
        for g in definition.group_by or ():
            if not isinstance(g, Variable):
                raise SiddhiAppCreationError("aggregation group by must be attributes")
            if g.attribute not in attr_types:
                raise DefinitionNotExistError(
                    f"group by attribute {g.attribute!r} not in {in_def.id!r}")
            self.group_attrs.append(g.attribute)

        # --- outputs: group attrs pass through; aggregator calls decompose ---
        self.outputs: list[_OutputSpec] = []
        self._comp_args: list = []  # compiled arg executor per flat component
        self._comp_meta: list = []  # Component per flat component
        sel = definition.selector
        for oa in sel.attributes:
            name = oa.rename or self._infer_name(oa.expression)
            expr = oa.expression
            if isinstance(expr, Variable) and expr.attribute in self.group_attrs:
                self.outputs.append(_OutputSpec(
                    name=name, type=attr_types[expr.attribute],
                    is_group=True, group_attr=expr.attribute))
                continue
            if isinstance(expr, AttributeFunction):
                factory = registry.lookup(ExtensionKind.AGGREGATOR,
                                          expr.namespace, expr.name)
                if isinstance(factory, AggregatorFactory):
                    args = [compile_expression(p, self.resolver, registry)
                            for p in expr.parameters]
                    spec = factory.make([a.type for a in args])
                    if spec.custom_scan is not None:
                        # distinctCount et al. don't decompose into additive
                        # bucket components (reference gets per-bucket distinct
                        # sets via its incremental aggregator SPI — not built)
                        raise SiddhiAppCreationError(
                            f"aggregation {definition.id!r}: {expr.name!r} is "
                            "not supported in incremental aggregations")
                    off = len(self._comp_meta)
                    for comp in spec.components:
                        self._comp_meta.append(comp)
                        self._comp_args.append(args[0] if args else None)
                    self.outputs.append(_OutputSpec(
                        name=name, type=spec.return_type, spec=spec,
                        comp_offset=off))
                    continue
            raise SiddhiAppCreationError(
                f"aggregation {definition.id!r} select item {name!r}: must be "
                "a group-by attribute or an aggregator call (the reference's "
                "last-value semantics for other attributes is not supported)")

        # --- output frame (the store-query surface) ---
        out_attrs = [Attribute(o.name, o.type) for o in self.outputs]
        out_attrs.append(Attribute(AGG_TIMESTAMP, AttributeType.LONG))
        self.output_attr_types = {a.name: a.type for a in out_attrs}
        self.output_definition = StreamDefinition(
            id=definition.id, attributes=tuple(out_attrs))
        self.output_codec = StreamCodec(self.output_definition, ctx.global_strings)
        # group attr name -> stored column dtype (store group cols under their
        # INPUT attribute name so duplicates across outputs share storage)
        self._group_layout = {g: dtypes.device_dtype(attr_types[g])
                              for g in self.group_attrs}

        self.capacity = max(ctx.effective_group_capacity, 4096)
        self.state = tuple(self._init_store() for _ in self.durations)
        self._batches_since_check = 0
        #: retention per duration (@purge/@retentionPeriod), ms; None = keep
        self.retention_ms = self._parse_retention(definition)

        # --- distributed (sharded) mode over a device mesh ---
        # The reference's `isDistributed` (AggregationRuntime.java:87,266,384):
        # each shard owns the (bucket, group) rows whose GROUP-key hash lands
        # on it, scatters locally, and `find()` merges shard stores. Here the
        # duration stores gain a leading mesh-sharded shard axis; ingest runs
        # as one shard_map step (each shard masks the replicated batch down to
        # its keys), and reads flatten [n_shards, K] -> [n_shards*K] — the
        # flatten IS the gather, inserted by XLA where the read computes.
        self.mesh = getattr(ctx, "mesh", None) if self.group_attrs else None
        self.n_shards = 1
        if self.mesh is not None:
            self.n_shards = self.mesh.shape[self.mesh.axis_names[0]]
        self._build_steps()
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.sharded import stack_states

            sharding = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
            self.state = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding),
                stack_states(self.state, self.n_shards))

        # --- durable duration stores (@store on the aggregation) ---
        # reference: aggregations persist per-duration tables
        # (<AggName>_SECONDS, ...) in an external store and REBUILD their
        # in-memory buckets from them on restart
        # (core/aggregation/IncrementalExecutorsInitialiser.java). Here:
        # `@store(type='x', ...)` on `define aggregation` creates one
        # RecordStore per duration; flush_durable() (shutdown + persist)
        # writes bucket rows through, and construction rebuilds from any
        # rows found.
        self._durable_stores = None
        #: rows held back at a capacity-truncated rebuild, re-merged at flush
        self._unrestored: dict = {}
        store_ann = next((a for a in (definition.annotations or ())
                          if a.name.lower() == "store"), None)
        if store_ann is not None:
            self._init_durable(store_ann)

        input_junction.subscribe(self)
        if self._durable_stores is not None:
            self._rebuild_from_durable()

    def _init_durable(self, ann) -> None:
        from ..extension.registry import ExtensionKind
        from ..io.record_table import RecordStore
        from ..query_api.definition import Attribute, TableDefinition

        props = {e.key: e.value for e in ann.elements if e.key}
        store_type = props.pop("type", None)
        if not store_type:
            raise SiddhiAppCreationError(
                f"aggregation {self.definition.id!r}: @store needs "
                "type='<registered store>'")
        factory = self.ctx.registry.require(ExtensionKind.STORE, "",
                                            store_type)
        self._durable_stores = {}
        attrs = [Attribute(AGG_TIMESTAMP, AttributeType.LONG)]
        attrs += [Attribute(g, self._group_attr_type(g))
                  for g in self.group_attrs]
        for ci in range(len(self._comp_meta)):
            attrs.append(Attribute(f"_c{ci}", AttributeType.DOUBLE))
        for dur in self.durations:
            td = TableDefinition(
                id=f"{self.definition.id}_{dur.value}",
                attributes=tuple(attrs))
            store: RecordStore = factory()
            store.init(td, dict(props),
                       self.ctx.config_reader(f"store:{store_type}")
                       if hasattr(self.ctx, "config_reader") else None)
            store.connect()
            self._durable_stores[dur] = store

    def _group_attr_type(self, name):
        in_def = self.junction.definition
        for a in in_def.attributes:
            if a.name == name:
                return a.type
        raise DefinitionNotExistError(name)

    def export_rows(self) -> dict:
        """Decode every duration store into host bucket rows:
        {duration: [ {AGG_TIMESTAMP, <groups...>, _c0.._cN} ]}."""
        import numpy as np
        out = {}
        for d_idx, dur in enumerate(self.durations):
            flat = self.state[d_idx]
            if self.n_shards > 1:  # drop the shard axis: disjoint union
                flat = jax.tree_util.tree_map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), flat)
            alive = np.asarray(flat.alive)
            bts = np.asarray(flat.bucket_ts)
            groups = {g: np.asarray(v) for g, v in flat.group_cols.items()}
            comps = [np.asarray(c) for c in flat.comps]
            gtypes = {g: self._group_attr_type(g) for g in self.group_attrs}
            rows = []
            for i in np.nonzero(alive)[0]:
                row = {AGG_TIMESTAMP: int(bts[i])}
                for g, arr in groups.items():
                    v = arr[i]
                    if gtypes[g] == AttributeType.STRING:
                        row[g] = self.ctx.global_strings.decode(int(v))
                    else:
                        row[g] = v.item()
                for ci, c in enumerate(comps):
                    row[f"_c{ci}"] = float(c[i])
                rows.append(row)
            out[dur] = rows
        return out

    def flush_durable(self) -> None:
        """Overwrite the durable duration tables with the current buckets.
        If the last REBUILD truncated (more durable rows than device
        capacity), merge instead — an authoritative overwrite would
        permanently erase the buckets that never fit."""
        if self._durable_stores is None:
            return
        exported = self.export_rows()
        for dur, store in self._durable_stores.items():
            tid = f"{self.definition.id}_{dur.value}"
            rows = exported[dur]
            held = self._unrestored.get(dur)
            if held:
                # buckets held back at a capacity-truncated rebuild re-join
                # the durable set (device rows win on key collisions);
                # retention uses the STREAM clock — wall time would wrongly
                # purge playback/external-time apps
                def _k(r):
                    return (r[AGG_TIMESTAMP],
                            tuple(r[g] for g in self.group_attrs))
                merged = {_k(r): r for r in held}
                for r in rows:
                    merged[_k(r)] = r
                rows = list(merged.values())
                retention = self.retention_ms.get(dur)
                if retention is not None:
                    cutoff = (self.ctx.timestamp_generator.current_time()
                              - retention)
                    rows = [r for r in rows if r[AGG_TIMESTAMP] >= cutoff]
            store.delete(store.compile_condition(None, tid))
            if rows:
                store.add(rows)

    def close_durable(self) -> None:
        if self._durable_stores is None:
            return
        for store in self._durable_stores.values():
            store.disconnect()

    def _rebuild_from_durable(self) -> None:
        """Re-scatter durable rows into fresh device stores (the reference's
        IncrementalExecutorsInitialiser restart path)."""
        import numpy as np
        gtypes = {g: self._group_attr_type(g) for g in self.group_attrs}
        for d_idx, dur in enumerate(self.durations):
            store = self._durable_stores.get(dur)
            if store is None:
                continue
            rows = list(store.find(store.compile_condition(
                None, f"{self.definition.id}_{dur.value}")))
            if not rows:
                continue
            fit = int(0.7 * self.capacity * self.n_shards)
            if len(rows) > fit:
                # restore the NEWEST buckets that fit; hold the rest
                # host-side so flush_durable never erases them
                rows.sort(key=lambda r: r[AGG_TIMESTAMP], reverse=True)
                self._unrestored[dur] = rows[fit:]
                rows = rows[:fit]
                import warnings
                warnings.warn(
                    f"aggregation {self.definition.id!r} [{dur.value}]: "
                    f"{len(self._unrestored[dur])} durable buckets exceed "
                    "device capacity on rebuild; oldest held host-side "
                    "(raise group_capacity)", stacklevel=2)
            n = len(rows)
            bts = np.asarray([r[AGG_TIMESTAMP] for r in rows], np.int64)
            gcols = {}
            for g in self.group_attrs:
                if gtypes[g] == AttributeType.STRING:
                    gcols[g] = np.asarray(
                        [self.ctx.global_strings.encode(r[g]) for r in rows],
                        np.int32)
                else:
                    gcols[g] = np.asarray(
                        [r[g] for r in rows],
                        dtypes.numpy_dtype(gtypes[g]))
            comps = [np.asarray([r[f"_c{ci}"] for r in rows], np.float64)
                     for ci in range(len(self._comp_meta))]
            new_store, n_restored = self._restore_fn(d_idx)(
                self.state[d_idx], jnp.asarray(bts),
                {g: jnp.asarray(v) for g, v in gcols.items()},
                [jnp.asarray(c) for c in comps], jnp.int32(n))
            if int(n_restored) < n:
                import warnings
                warnings.warn(
                    f"aggregation {self.definition.id!r} [{dur.value}]: only "
                    f"{int(n_restored)}/{n} durable buckets fit the "
                    f"{'sharded ' if self.mesh is not None else ''}store "
                    "capacity on rebuild — raise group_capacity",
                    stacklevel=2)
            self._replace_store(d_idx, new_store)

    def _restore_fn(self, d_idx):
        """Jitted bulk scatter of restored rows into one duration store."""
        group_attrs = self.group_attrs
        comp_meta = self._comp_meta
        K = self.capacity
        mesh = self.mesh
        n_shards = self.n_shards

        def restore(store: DurationStore, bts, gcols, comps, valid):
            keyparts = [bts] + [gcols[g] for g in group_attrs]
            key = hash_columns(keyparts)
            kt, ids, kres = key_lookup_or_insert(store.key_table, key, valid)
            widx = jnp.where(valid & kres, ids, K)
            new_bucket = store.bucket_ts.at[widx].set(bts, mode="drop")
            new_group = {g: store.group_cols[g].at[widx].set(
                gcols[g], mode="drop") for g in group_attrs}
            new_alive = store.alive.at[widx].set(True, mode="drop")
            new_comps = []
            for ci, comp in enumerate(comp_meta):
                new_comps.append(store.comps[ci].at[widx].set(
                    comps[ci].astype(comp.dtype), mode="drop"))
            n_ok = jnp.sum(valid & kres, dtype=jnp.int32)
            return DurationStore(kt, new_bucket, new_group,
                                 tuple(new_comps), new_alive), n_ok

        def plain_restore(store, bts, gcols, comps, n):
            valid = jnp.arange(bts.shape[0]) < n
            return restore(store, bts, gcols, comps, valid)

        if mesh is not None:
            # re-scatter restored rows to their OWNING shard by group hash —
            # the same ownership rule the sharded ingest uses
            # (parallel/sharded.shard_owned), so a restored mesh app starts
            # balanced instead of piling every durable row onto shard 0
            def sharded_restore(store, bts, gcols, comps, n):
                valid = jnp.arange(bts.shape[0]) < n
                keys = hash_columns([gcols[g] for g in group_attrs])
                shard_of = keys.astype(jnp.uint32) % jnp.uint32(n_shards)

                def one(local, sidx):
                    return restore(local, bts, gcols, comps,
                                   valid & (shard_of == sidx))

                new_store, n_ok = jax.vmap(one, in_axes=(0, 0))(
                    store, jnp.arange(n_shards, dtype=jnp.uint32))
                return new_store, jnp.sum(n_ok, dtype=jnp.int32)

            return jax.jit(sharded_restore)
        return jax.jit(plain_restore)

    def _build_steps(self) -> None:
        """(Re)build the jitted ingest/evict for the current capacity —
        plain single-device, or shard_map over the mesh in distributed
        mode."""
        if self.mesh is None:
            self._ingest = jax.jit(self._make_ingest(), donate_argnums=(0,))
            self._evict = jax.jit(self._make_evict())
            return
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = self.mesh
        axis = mesh.axis_names[0]
        n_shards = self.n_shards
        group_attrs = self.group_attrs
        ingest = self._make_ingest()

        def shard_ingest(state, batch: EventBatch, now):
            from ..parallel.sharded import shard_owned

            local = jax.tree_util.tree_map(lambda x: x[0], state)
            mine = shard_owned(batch, [batch.cols[g] for g in group_attrs],
                               axis, n_shards)
            local = ingest(local, mine, now)
            return jax.tree_util.tree_map(lambda x: x[None], local)

        self._ingest = jax.jit(
            shard_map(shard_ingest, mesh=mesh,
                      in_specs=(P(axis), P(), P()), out_specs=P(axis),
                      check_vma=False),
            donate_argnums=(0,))

        def shard_ingest_lanes(state, batch: EventBatch, now):
            # per-host sharded ingestion: the batch arrives LANE-SHARDED
            # (each shard holds only its own pre-routed rows —
            # parallel/multihost.global_lane_batch). shard_owned stays as a
            # guard: mis-routed rows are dropped, never double-counted.
            from ..parallel.sharded import shard_owned

            local = jax.tree_util.tree_map(lambda x: x[0], state)
            mine = shard_owned(batch, [batch.cols[g] for g in group_attrs],
                               axis, n_shards)
            local = ingest(local, mine, now)
            return jax.tree_util.tree_map(lambda x: x[None], local)

        self._ingest_lanes = jax.jit(
            shard_map(shard_ingest_lanes, mesh=mesh,
                      in_specs=(P(axis), P(axis), P()), out_specs=P(axis),
                      check_vma=False),
            donate_argnums=(0,))
        self._evict = jax.jit(jax.vmap(self._make_evict(), in_axes=(0, 0)))

    def ingest_global(self, batch: EventBatch, now: int) -> None:
        """Ingest a LANE-SHARDED global EventBatch (per-host sharded
        ingestion over a multi-host mesh: every process calls this with the
        same global program; each contributed only its own rows —
        parallel/multihost.global_lane_batch). Requires mesh mode."""
        import jax.numpy as jnp
        if self.mesh is None:
            raise SiddhiAppCreationError(
                "ingest_global needs a mesh-enabled aggregation "
                "(create the runtime with mesh=...)")
        self.state = self._ingest_lanes(self.state, batch, jnp.int64(now))

    @staticmethod
    def _parse_retention(definition) -> dict:
        """@purge(enable='true', @retentionPeriod(sec='120 min', min='24 hours',
        ...)) (reference: core/aggregation/IncrementalDataPurger.java)."""
        from .partition import _parse_annotation_time
        out: dict[Duration, int] = {}
        ann = next((a for a in definition.annotations or ()
                    if a.name.lower() == "purge"), None)
        if ann is None or (ann.element("enable") or "true").lower() == "false":
            return out
        rp = ann.nested_annotation("retentionPeriod")
        if rp is None:
            return out
        for e in rp.elements:
            if e.key:
                out[Duration.parse(e.key)] = _parse_annotation_time(e.value)
        return out

    # ------------------------------------------------------------------ build

    @staticmethod
    def _infer_name(expr: Expression) -> str:
        if isinstance(expr, Variable):
            return expr.attribute
        if isinstance(expr, AttributeFunction):
            return expr.name
        raise SiddhiAppCreationError(
            "aggregation select items need `as` names for expressions")

    def _init_store(self) -> DurationStore:
        K = self.capacity
        return DurationStore(
            key_table=init_key_table(K),
            bucket_ts=jnp.zeros((K,), jnp.int64),
            group_cols={g: jnp.zeros((K,), dt)
                        for g, dt in self._group_layout.items()},
            comps=tuple(jnp.zeros((K,), c.dtype) if c.op == "sum"
                        else jnp.full((K,), _monotone_identity(c), c.dtype)
                        for c in self._comp_meta),
            alive=jnp.zeros((K,), bool),
        )

    def _make_ingest(self):
        durations = self.durations
        frame_ref = self.frame_ref
        ts_attr = self.ts_attr
        group_attrs = self.group_attrs
        comp_meta = self._comp_meta
        comp_args = self._comp_args
        K = self.capacity

        def ingest(state, batch: EventBatch, now):
            scope = Scope()
            scope.add_frame(frame_ref, batch.cols, batch.ts, batch.valid,
                            default=True)
            scope.extras["now"] = now
            ts_src = (batch.cols[ts_attr] if ts_attr is not None else batch.ts)
            ts_src = ts_src.astype(jnp.int64)
            sign = jnp.ones_like(batch.ts, dtype=jnp.float32)
            arg_vals = [a(scope) if a is not None else None for a in comp_args]
            deltas = [c.delta(v, sign) for c, v in zip(comp_meta, arg_vals)]

            new_state = []
            for d_idx, dur in enumerate(durations):
                store: DurationStore = state[d_idx]
                bucket = bucket_start(dur, ts_src)
                keyparts = [bucket] + [batch.cols[g] for g in group_attrs]
                key = hash_columns(keyparts)
                kt, ids, kres = key_lookup_or_insert(
                    store.key_table, key, batch.valid)
                widx = jnp.where(batch.valid & kres, ids, K)
                new_bucket_ts = store.bucket_ts.at[widx].set(bucket, mode="drop")
                new_group = {g: store.group_cols[g].at[widx].set(
                    batch.cols[g], mode="drop") for g in group_attrs}
                new_alive = store.alive.at[widx].set(True, mode="drop")
                new_comps = []
                for ci, comp in enumerate(comp_meta):
                    acc = store.comps[ci]
                    d = deltas[ci].astype(acc.dtype)
                    if comp.op == "sum":
                        acc = acc.at[widx].add(d, mode="drop")
                    elif comp.op == "min":
                        acc = acc.at[widx].min(d, mode="drop")
                    else:
                        acc = acc.at[widx].max(d, mode="drop")
                    new_comps.append(acc)
                new_state.append(DurationStore(
                    kt, new_bucket_ts, new_group, tuple(new_comps), new_alive))
            return tuple(new_state)

        return ingest

    def _make_evict(self):
        """(store, cutoff) -> store' keeping only buckets >= cutoff, with a
        rebuilt key table (the reference's IncrementalDataPurger deletes rows
        from duration tables; here we re-hash the kept slots into a fresh
        store — one fused gather/scatter)."""
        group_attrs = self.group_attrs
        comp_meta = self._comp_meta
        K = self.capacity
        layout = self._group_layout

        def evict(store: DurationStore, cutoff):
            keep = store.alive & (store.bucket_ts >= cutoff)
            keys = hash_columns([store.bucket_ts]
                                + [store.group_cols[g] for g in group_attrs])
            kt, ids, kres = key_lookup_or_insert(init_key_table(K), keys, keep)
            widx = jnp.where(keep & kres, ids, K)
            new_bucket = jnp.zeros((K,), jnp.int64).at[widx].set(
                store.bucket_ts, mode="drop")
            new_group = {g: jnp.zeros((K,), layout[g]).at[widx].set(
                store.group_cols[g], mode="drop") for g in group_attrs}
            new_alive = jnp.zeros((K,), bool).at[widx].set(True, mode="drop")
            new_comps = []
            for ci, comp in enumerate(comp_meta):
                base = (jnp.zeros((K,), comp.dtype) if comp.op == "sum"
                        else jnp.full((K,), _monotone_identity(comp), comp.dtype))
                new_comps.append(base.at[widx].set(store.comps[ci], mode="drop"))
            return DurationStore(kt, new_bucket, new_group, tuple(new_comps),
                                 new_alive)

        return evict

    def _replace_store(self, d_idx: int, store: DurationStore) -> None:
        state = list(self.state)
        state[d_idx] = store
        self.state = tuple(state)

    def _grow(self) -> None:
        """Double every duration store's capacity (one retrace + rehash each).
        Taken when eviction cannot free slots — high *group* cardinality
        rather than bucket age (the reference grows its HashMaps the same way,
        implicitly)."""
        import warnings
        self.capacity *= 2
        warnings.warn(
            f"aggregation {self.definition.id!r}: growing bucket stores to "
            f"{self.capacity} slots (set group_capacity higher to avoid the "
            "rehash)", stacklevel=2)
        self._build_steps()
        # rehash every store into the new capacity (cutoff far in the past
        # keeps everything)
        keep_all = (jnp.full((self.n_shards,), -(1 << 62), jnp.int64)
                    if self.mesh is not None else jnp.int64(-(1 << 62)))
        self.state = tuple(
            self._evict(store, keep_all) for store in self.state)

    def _maybe_evict(self, now: int) -> None:
        """Retention purge + capacity-pressure handling: evict buckets older
        than the newest half when age explains the pressure, grow the store
        when group cardinality does — never silently drop or corrupt.

        All statistics are PER SHARD (capacity is a per-shard quantity in
        distributed mode; global math here would over-evict by ~n_shards)."""
        import numpy as np
        S, K = self.n_shards, self.capacity
        grow = False
        for d_idx, dur in enumerate(self.durations):
            store = self.state[d_idx]
            retention = self.retention_ms.get(dur)
            base_cutoff = (now - retention) if retention is not None else 0
            counts = np.atleast_1d(np.asarray(store.key_table.count))
            pressure = int(counts.max()) > int(0.85 * K)
            if retention is None and not pressure:
                # fast path: only the scalar count crosses to the host
                continue
            alive = np.asarray(store.alive).reshape(S, K)
            bts = np.asarray(store.bucket_ts).reshape(S, K)
            cutoffs = np.full((S,), base_cutoff, dtype=np.int64)
            for s in range(S):
                if int(counts[s]) <= int(0.85 * K):
                    continue
                live_b = np.sort(bts[s][alive[s]])[::-1]
                pressure_cutoff = int(live_b[:K // 2][-1])
                would_keep = int(
                    (live_b >= max(base_cutoff, pressure_cutoff)).sum())
                if would_keep > int(0.7 * K):
                    grow = True  # eviction can't help: too many live groups
                else:
                    cutoffs[s] = max(cutoffs[s], pressure_cutoff)
                    import warnings
                    warnings.warn(
                        f"aggregation {self.definition.id!r} [{dur.value}]"
                        f"{f' shard {s}' if S > 1 else ''}: store at "
                        f"capacity; evicting buckets older than "
                        f"{pressure_cutoff} (raise group_capacity or add "
                        "@purge retention)", stacklevel=2)
            evictable = (alive & (bts < cutoffs[:, None])).any()
            if (cutoffs > 0).any() and evictable:
                arg = (jnp.asarray(cutoffs) if self.mesh is not None
                       else jnp.int64(int(cutoffs[0])))
                self._replace_store(d_idx, self._evict(store, arg))
        if grow:
            self._grow()

    # ---------------------------------------------------------------- runtime

    def on_batch(self, batch: EventBatch, now: int) -> None:
        cap = self.junction.batch_size
        if batch.capacity < cap:
            # the jitted ingest is traced at the junction capacity; widen
            # shape-bucketed deliveries back (new lanes invalid)
            batch = batch.pad_to(cap)
        self.state = self._ingest(self.state, batch, jnp.int64(now))
        self._batches_since_check += 1
        if self._batches_since_check >= 32:
            self._batches_since_check = 0
            self._maybe_evict(now)

    # ------------------------------------------------------------------- find

    def duration_index(self, per) -> int:
        if isinstance(per, Expression):
            if not isinstance(per, Constant):
                raise SiddhiAppCreationError("per must be a constant duration")
            per = per.value
        if isinstance(per, str):
            per = Duration.parse(per)
        if per not in self.durations:
            raise SiddhiAppCreationError(
                f"aggregation {self.definition.id!r} has no duration {per}; "
                f"available: {[d.value for d in self.durations]}")
        return self.durations.index(per)

    def store_contents(self, store: DurationStore, now,
                       within: Optional[tuple[int, int]] = None):
        """Output-frame view over one duration's store: (cols, ts, valid) —
        the findable surface for store queries and joins (reference:
        AggregationRuntime.find / compileExpression:384+). In distributed
        mode the store arrives with a leading shard axis; flattening it to
        [n_shards*K] is the shard-merged `find()` — rows are disjoint across
        shards (group-hash ownership), so the union needs no combining."""
        if store.bucket_ts.ndim == 2:
            store = jax.tree_util.tree_map(
                lambda x: x.reshape((-1,) + x.shape[2:]), store)
        cols = {}
        for o in self.outputs:
            if o.is_group:
                cols[o.name] = store.group_cols[o.group_attr]
            else:
                parts = [store.comps[o.comp_offset + i]
                         for i in range(len(o.spec.components))]
                cols[o.name] = o.spec.finalize(parts)
        cols[AGG_TIMESTAMP] = store.bucket_ts
        valid = store.alive
        if within is not None:
            valid = valid & (store.bucket_ts >= jnp.int64(within[0])) \
                & (store.bucket_ts < jnp.int64(within[1]))
        return cols, store.bucket_ts, valid

    def view(self, per, within_range=None) -> "_AggregationView":
        """Bind a `per` duration (+ optional within bounds) into a store-like
        object OnDemandQueryRuntime / joins can probe."""
        d_idx = self.duration_index(per)
        within = None
        if within_range is not None:
            lo = parse_time_constant(_const_value(within_range[0]))
            if within_range[1] is None:
                # single-value within: the whole bucket containing the instant
                # (reference's `within <point>` form)
                lo, hi = bucket_bounds(self.durations[d_idx], lo)
            else:
                hi = parse_time_constant(_const_value(within_range[1]))
            within = (lo, hi)
        return _AggregationView(self, d_idx, within)


def _monotone_identity(comp):
    if comp.op == "min":
        return (jnp.iinfo(comp.dtype).max
                if jnp.issubdtype(comp.dtype, jnp.integer) else jnp.inf)
    return (jnp.iinfo(comp.dtype).min
            if jnp.issubdtype(comp.dtype, jnp.integer) else -jnp.inf)


def _const_value(expr):
    if isinstance(expr, Constant):
        return expr.value
    if isinstance(expr, (int, str)):
        return expr
    raise SiddhiAppCreationError(f"within bound must be a constant, got {expr!r}")


class _AggregationView:
    """Store adapter: quacks like a named window for OnDemandQueryRuntime
    (definition / attr_types / codec / state / contents)."""

    def __init__(self, runtime: AggregationRuntime, d_idx: int,
                 within: Optional[tuple[int, int]]) -> None:
        self.runtime = runtime
        self.d_idx = d_idx
        self.within = within
        self.definition = runtime.output_definition
        self.attr_types = dict(runtime.output_attr_types)
        self.codec = runtime.output_codec

    @property
    def state(self):
        return self.runtime.state[self.d_idx]

    def contents(self, store, now):
        return self.runtime.store_contents(store, now, self.within)
