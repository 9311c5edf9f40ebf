"""Attribute aggregators, decomposed into grouped-scan components.

Reference: the 13 aggregator executors under
core/query/selector/attribute/aggregator/ (SumAttributeAggregatorExecutor.java:69
et al.) each keep a per-group-key mutable state with processAdd/processRemove.
TPU re-design: an aggregator is a set of *components*, each a per-key
accumulator driven by ops/groupby.grouped_scan with signed per-lane deltas
(CURRENT lanes add, EXPIRED lanes subtract, RESET lanes epoch-bump), plus a
`finalize` combining component values per lane. avg = sum/count, stdDev =
(sumsq, sum, count), and/or = counts of false/true — all become fused scans.

min/max are monotone scans (op="min"/"max"); they cannot process removals, so
the planner rejects them over sliding windows (matching limitation called out
in SURVEY §7; a segment-tree ring is the planned upgrade).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtypes
from ..errors import SiddhiAppCreationError
from ..extension.registry import GLOBAL, ExtensionKind
from ..query_api.definition import AttributeType

_T = AttributeType


@dataclass(frozen=True)
class Component:
    """One per-key accumulator: delta(args_value_array, sign) -> [L] deltas."""

    dtype: object
    delta: Callable  # (vals: [L] or None, sign: [L] ±1 float) -> [L] deltas
    op: str = "sum"
    #: monotone components ignore EXPIRED lanes (sign<0) instead of erroring
    ignore_removal: bool = False
    #: survives RESET (minForever/maxForever)
    ignore_reset: bool = False


@dataclass(frozen=True)
class AggregatorSpec:
    components: tuple[Component, ...]
    finalize: Callable  # (list of per-lane component arrays) -> [L] values
    return_type: AttributeType
    #: needs removal support (sliding windows); min/max set False
    supports_removal: bool = True
    #: stateful aggregators that don't decompose into scan components
    #: (distinctCount): init_custom(group_capacity, grouped) -> state pytree;
    #: custom_scan(state, slots, arg_vals, sign, lane_valid, resets, epoch,
    #: grouped) -> (state', per-lane values). `grouped` is a static planner
    #: hint: ungrouped queries pass all-zero slots, which admits cheaper
    #: state layouts.
    init_custom: Optional[Callable] = None
    custom_scan: Optional[Callable] = None
    #: 'min'/'max' — marks true-extrema aggregators so sliding-window
    #: planners can swap in the removal-capable range-query path (the
    #: monotone component scan cannot undo removals)
    extrema_op: Optional[str] = None
    #: custom_scan is a fold over the lanes in order, exact in integers:
    #: a chunk cut into consecutive calls gives the same per-lane values
    #: and the same state, bit for bit (ops/selector.py `lane_sequential`)
    lane_sequential: bool = False


class AggregatorFactory:
    """SPI: make(arg_types) -> AggregatorSpec."""

    def __init__(self, make: Callable):
        self.make = make


def _sum_return_type(t: AttributeType) -> AttributeType:
    # reference SumAttributeAggregatorExecutor: int/long -> LONG, float/double -> DOUBLE
    return _T.LONG if t in (_T.INT, _T.LONG) else _T.DOUBLE


def _make_sum(arg_types):
    t = arg_types[0]
    if not dtypes.is_numeric(t):
        raise SiddhiAppCreationError(f"sum() over non-numeric {t}")
    rt = _sum_return_type(t)
    dt = dtypes.device_dtype(rt)
    comp = Component(dtype=dt, delta=lambda v, sign: v.astype(dt) * sign.astype(dt))
    return AggregatorSpec((comp,), lambda cs: cs[0], rt)


def _make_count(arg_types):
    dt = dtypes.device_dtype(_T.LONG)
    comp = Component(dtype=dt, delta=lambda v, sign: sign.astype(dt))
    return AggregatorSpec((comp,), lambda cs: cs[0], _T.LONG)


def _make_avg(arg_types):
    t = arg_types[0]
    if not dtypes.is_numeric(t):
        raise SiddhiAppCreationError(f"avg() over non-numeric {t}")
    dt = dtypes.device_dtype(_T.DOUBLE)
    s = Component(dtype=dt, delta=lambda v, sign: v.astype(dt) * sign.astype(dt))
    c = Component(dtype=dt, delta=lambda v, sign: sign.astype(dt))

    def fin(cs):
        total, n = cs
        return jnp.where(n != 0, total / jnp.where(n != 0, n, 1), jnp.zeros_like(total))

    return AggregatorSpec((s, c), fin, _T.DOUBLE)


def _make_minmax(op: str):
    def make(arg_types):
        t = arg_types[0]
        if not dtypes.is_numeric(t):
            raise SiddhiAppCreationError(f"{op}() over non-numeric {t}")
        dt = dtypes.device_dtype(t)
        comp = Component(dtype=dt, delta=lambda v, sign: v.astype(dt), op=op,
                         ignore_removal=True)
        return AggregatorSpec((comp,), lambda cs: cs[0], t,
                              supports_removal=False, extrema_op=op)

    return make


def _make_minmax_forever(op: str):
    def make(arg_types):
        t = arg_types[0]
        dt = dtypes.device_dtype(t)
        comp = Component(dtype=dt, delta=lambda v, sign: v.astype(dt), op=op,
                         ignore_removal=True, ignore_reset=True)
        return AggregatorSpec((comp,), lambda cs: cs[0], t, supports_removal=True)

    return make


def _make_stddev(arg_types):
    t = arg_types[0]
    if not dtypes.is_numeric(t):
        raise SiddhiAppCreationError(f"stdDev() over non-numeric {t}")
    dt = dtypes.device_dtype(_T.DOUBLE)
    s = Component(dtype=dt, delta=lambda v, sign: v.astype(dt) * sign.astype(dt))
    s2 = Component(dtype=dt, delta=lambda v, sign: (v.astype(dt) ** 2) * sign.astype(dt))
    c = Component(dtype=dt, delta=lambda v, sign: sign.astype(dt))

    def fin(cs):
        total, sumsq, n = cs
        safe_n = jnp.where(n != 0, n, 1)
        mean = total / safe_n
        var = sumsq / safe_n - mean * mean
        # population std dev (reference StdDevAttributeAggregatorExecutor)
        return jnp.where(n != 0, jnp.sqrt(jnp.maximum(var, 0.0)), jnp.zeros_like(var))

    return AggregatorSpec((s, s2, c), fin, _T.DOUBLE)


def _make_bool_and(arg_types):
    # and(bool): true while no false values in window — count falses
    dt = dtypes.device_dtype(_T.LONG)
    c = Component(dtype=dt, delta=lambda v, sign: jnp.where(~v, sign.astype(dt), 0))

    def fin(cs):
        return cs[0] == 0

    return AggregatorSpec((c,), fin, _T.BOOL)


def _make_bool_or(arg_types):
    dt = dtypes.device_dtype(_T.LONG)
    c = Component(dtype=dt, delta=lambda v, sign: jnp.where(v, sign.astype(dt), 0))

    def fin(cs):
        return cs[0] > 0

    return AggregatorSpec((c,), fin, _T.BOOL)


def _make_distinct_count(arg_types):
    """distinctCount(attr) — EXACT distinct values per group with full
    add/remove support (reference: DistinctCountAttributeAggregatorExecutor
    keeps a value→count HashMap per group key).

    TPU design: one device hash table over (group, value) PAIRS shared by all
    groups + a per-group distinct counter. Two chained grouped scans per
    batch: (1) per-pair signed counts — a CURRENT lane whose post-update pair
    count == 1 is a 0→1 transition (+1 distinct), an EXPIRED lane reaching 0
    is a 1→0 transition (-1); (2) those ±1 deltas scanned per group give the
    per-lane running distinct count, preserving the reference's event-at-a-time
    emission semantics inside a batch.

    Fast path: an UNGROUPED distinctCount over a STRING attribute needs no
    hash table at all — device strings are dictionary codes, already dense
    ids into the interning table, so the code indexes the pair-count table
    directly (codes ≥ capacity are dropped with the same documented overflow
    semantics; the runtime monitors interning size against capacity)."""
    from .groupby import (
        grouped_scan,
        hash_columns,
        init_group_state,
        init_key_table,
        key_lookup_or_insert,
        ungrouped_scan,
    )

    dt = dtypes.device_dtype(_T.LONG)
    code_arg = bool(arg_types) and arg_types[0] == _T.STRING

    def init_custom(group_capacity: int, grouped: bool = True):
        P = group_capacity  # (group, value) pair capacity
        if code_arg and not grouped:
            return (init_group_state(P, dt), init_group_state(1, dt))
        return (init_key_table(P), init_group_state(P, dt),
                init_group_state(group_capacity, dt))

    def custom_scan(state, slots, arg_vals, sign, lane_valid, resets, epoch,
                    grouped: bool = True):
        deltas = sign.astype(dt)
        if code_arg and not grouped:
            pair_counts, distinct = state
            P = pair_counts.values.shape[0]
            code = arg_vals[0].astype(jnp.int32)
            ok = lane_valid & (code >= 0) & (code < P)
            pair_counts2, pair_post = grouped_scan(
                pair_counts, code, deltas, ok, resets, epoch, op="sum")
            dd = jnp.where(sign > 0,
                           (pair_post == 1).astype(dt),
                           -(pair_post == 0).astype(dt))
            distinct2, out = ungrouped_scan(
                distinct, dd, ok, resets, epoch, op="sum")
            return (pair_counts2, distinct2), out
        kt, pair_counts, distinct = state
        pk = hash_columns([slots.astype(jnp.int64), arg_vals[0]])
        kt2, pair_slots, kres = key_lookup_or_insert(kt, pk, lane_valid)
        # drop unresolved lanes entirely (pair table exhausted — monitored
        # truncation) instead of corrupting pair slot 0
        lane_valid = lane_valid & kres
        pair_counts2, pair_post = grouped_scan(
            pair_counts, pair_slots, deltas, lane_valid, resets, epoch,
            op="sum")
        dd = jnp.where(sign > 0,
                       (pair_post == 1).astype(dt),
                       -(pair_post == 0).astype(dt))
        if grouped:
            distinct2, out = grouped_scan(
                distinct, slots, dd, lane_valid, resets, epoch, op="sum")
        else:
            distinct2, out = ungrouped_scan(
                distinct, dd, lane_valid, resets, epoch, op="sum")
        return (kt2, pair_counts2, distinct2), out

    return AggregatorSpec((), lambda cs: cs[0], _T.LONG,
                          init_custom=init_custom, custom_scan=custom_scan,
                          lane_sequential=True)


class HLLState(NamedTuple):
    """hll:distinctCount sketch state; `dropped` counts lanes whose group
    slot exceeded config.hll_group_capacity (monitored overflow)."""

    regs: jax.Array  # int32[G * M] registers
    dropped: jax.Array  # int64 lifetime lanes with no sketch


def _make_hll_distinct_count(arg_types):
    """hll:distinctCount(attr) — APPROXIMATE distinct count via a
    HyperLogLog sketch (BASELINE.md config 3 names the HLL variant; the
    EXACT pair-table distinctCount stays the default `distinctCount`).

    m = config.hll_registers registers per group (standard error
    ~1.04/sqrt(m): 1024 → ~3.3%). Each CURRENT lane scatter-maxes one
    register with the rank of its value-hash; the per-group estimate is the
    classic alpha_m * m^2 / sum(2^-M) harmonic mean with the small-range
    linear-counting correction. Removals (sliding EXPIRED lanes) are
    IGNORED — a sketch cannot forget; use exact distinctCount where
    sliding-window removal matters. RESET (batch-window flush) clears the
    registers. Per-lane emission reports the POST-BATCH estimate
    (documented batch-granularity divergence from per-event emission)."""
    from .groupby import hash_columns

    dt = dtypes.device_dtype(_T.LONG)
    M = int(dtypes.config.hll_registers)
    P_BITS = M.bit_length() - 1
    assert M == 1 << P_BITS, "hll_registers must be a power of two"

    def init_custom(group_capacity: int, grouped: bool = True):
        G = (min(group_capacity, dtypes.config.hll_group_capacity)
             if grouped else 1)
        return HLLState(regs=jnp.zeros((G * M,), jnp.int32),
                        dropped=jnp.int64(0))

    def _estimate(regs):
        R = regs.reshape(-1, M).astype(jnp.float32)
        inv = jnp.sum(jnp.exp2(-R), axis=1)
        alpha = 0.7213 / (1.0 + 1.079 / M)
        E = alpha * M * M / inv
        zeros = jnp.sum(R == 0, axis=1)
        lin = M * jnp.log(M / jnp.maximum(zeros, 1).astype(jnp.float32))
        E = jnp.where((E <= 2.5 * M) & (zeros > 0), lin, E)
        return jnp.round(E).astype(dt)

    def custom_scan(state, slots, arg_vals, sign, lane_valid, resets, epoch,
                    grouped: bool = True):
        regs = state.regs
        G = regs.shape[0] // M
        h = hash_columns([arg_vals[0]]).astype(jnp.uint64)
        # murmur3 fmix64 avalanche: the column mix leaves low bits
        # correlated for dense inputs (string codes!), which skews both the
        # register index and the rank distribution
        h = h ^ (h >> 33)
        h = h * jnp.uint64(0xFF51AFD7ED558CCD)
        h = h ^ (h >> 33)
        h = h * jnp.uint64(0xC4CEB9FE1A85EC53)
        h = h ^ (h >> 33)
        j = (h & jnp.uint64(M - 1)).astype(jnp.int32)
        w = (h >> jnp.uint64(P_BITS)).astype(jnp.uint32)
        rho = jax.lax.clz(
            jax.lax.bitcast_convert_type(w, jnp.int32)) + 1
        in_cap = (slots >= 0) & (slots < G)
        ok = lane_valid & (sign > 0) & in_cap
        idx = jnp.where(ok, slots * M + j, G * M)
        sl = jnp.clip(slots, 0, G - 1)
        # group slots beyond hll_group_capacity track NO sketch: emit 0 and
        # count them (collect_overflow surfaces the counter with a warning)
        n_drop = jnp.sum(lane_valid & (sign > 0) & ~in_cap, dtype=jnp.int64)

        # RESET handling at lane position (batch-window flushes mid-chunk):
        # lanes BEFORE the first reset continue the incoming sketch; lanes
        # AFTER the last reset start a fresh one. Chunks holding >1 reset
        # approximate the middle segments with the final sketch's estimate
        # (documented — sketches are for large windows; a tiny batch window
        # flushing several times per chunk wants exact distinctCount).
        n_resets = jnp.sum(resets, dtype=jnp.int32)
        rk = jnp.cumsum(resets.astype(jnp.int32))
        before_first = rk == 0
        after_last = rk == n_resets

        regs_a = regs.at[jnp.where(before_first, idx, G * M)].max(
            rho, mode="drop")
        est_a = _estimate(regs_a)[sl]
        fresh = jnp.where(n_resets > 0, jnp.zeros_like(regs), regs)
        regs_b = fresh.at[jnp.where(after_last, idx, G * M)].max(
            rho, mode="drop")
        est_b = _estimate(regs_b)[sl]
        out = jnp.where(before_first & (n_resets > 0), est_a, est_b)
        out = jnp.where(in_cap, out, jnp.zeros_like(out))
        return HLLState(regs=regs_b, dropped=state.dropped + n_drop), out

    return AggregatorSpec((), lambda cs: cs[0], _T.LONG,
                          init_custom=init_custom, custom_scan=custom_scan)


_COMPACTION_INSERT = None


def _compaction_insert():
    """Module-cached jitted insert — a fresh jax.jit wrapper per compaction
    would retrace/recompile every time."""
    global _COMPACTION_INSERT
    if _COMPACTION_INSERT is None:
        from .groupby import key_lookup_or_insert
        _COMPACTION_INSERT = jax.jit(key_lookup_or_insert)
    return _COMPACTION_INSERT


def compact_distinct_state(state, current_epoch: int):
    """Evict dead pairs from a distinctCount hash-path state tuple.

    The pair table is append-only inside the jitted step (zeroed pairs keep
    their slot, unlike the reference's HashMap entry removal) — lifetime-
    unique (group,value) pairs eventually fill it. This host-triggered
    rebuild re-inserts only LIVE pairs (count != 0 at the current epoch)
    into a fresh table, reclaiming every dead slot. Mirrors the reference's
    natural HashMap removal and AggregationRuntime-style eviction rebuilds.

    Called by the runtime's capacity monitor, never from inside a step.
    """
    from .groupby import GroupState, init_key_table, key_lookup_or_insert

    kt, pair_counts, distinct = state
    H = kt.keys.shape[0]
    K = H // 2
    keys = np.asarray(kt.keys)
    ids = np.asarray(kt.ids)
    vals = np.asarray(pair_counts.values)
    eps = np.asarray(pair_counts.epoch)
    occupied = keys != np.iinfo(np.int64).max
    live = occupied & (vals[ids] != 0) & (eps[ids] == current_epoch)
    live_keys = keys[live]
    live_vals = vals[ids[live]]

    fresh = init_key_table(K)
    new_vals = np.zeros((K,), vals.dtype)
    insert = _compaction_insert()
    CH = 65536
    n = live_keys.shape[0]
    for i in range(0, max(n, 1), CH):
        chunk = live_keys[i:i + CH]
        if chunk.shape[0] == 0:
            break
        pad = CH - chunk.shape[0]
        ck = jnp.asarray(np.pad(chunk, (0, pad)))
        cv = jnp.ones((CH,), bool).at[CH - pad:].set(False) if pad else \
            jnp.ones((CH,), bool)
        fresh, new_ids, ok = insert(fresh, ck, cv)
        new_ids = np.asarray(new_ids)[:chunk.shape[0]]
        ok = np.asarray(ok)[:chunk.shape[0]]
        new_vals[new_ids[ok]] = live_vals[i:i + CH][ok]

    dt = pair_counts.values.dtype
    rebuilt = GroupState(
        values=jnp.asarray(new_vals, dt),
        epoch=jnp.full((K,), current_epoch,
                       pair_counts.epoch.dtype))
    return (fresh, rebuilt, distinct)


def _make_union_set(arg_types):
    """unionSet(set) — reference UnionSetAttributeAggregatorExecutor
    aggregates java.util.Sets. Host-opaque objects cannot ride device
    streams; the supported composition sizeOfSet(unionSet(createSet(x)))
    is rewritten to an exact distinctCount at plan time (ops/selector.py
    _rewrite_set_idioms) before this factory would ever run."""
    raise SiddhiAppCreationError(
        "unionSet() inside a larger expression is not supported on this "
        "engine (raw `select unionSet(x) as s` IS — the set materializes "
        "host-side at the callback boundary); "
        "use sizeOfSet(unionSet(...)), which compiles to an exact distinct "
        "count on device")


def register_all() -> None:
    reg = lambda name, make: GLOBAL.register(  # noqa: E731
        ExtensionKind.AGGREGATOR, "", name, AggregatorFactory(make))
    reg("sum", _make_sum)
    reg("count", _make_count)
    reg("avg", _make_avg)
    reg("min", _make_minmax("min"))
    reg("max", _make_minmax("max"))
    reg("minForever", _make_minmax_forever("min"))
    reg("maxForever", _make_minmax_forever("max"))
    reg("stdDev", _make_stddev)
    reg("and", _make_bool_and)
    reg("or", _make_bool_or)
    reg("distinctCount", _make_distinct_count)
    reg("unionSet", _make_union_set)
    GLOBAL.register(ExtensionKind.AGGREGATOR, "hll", "distinctCount",
                    AggregatorFactory(_make_hll_distinct_count))


register_all()
