"""Grouped segmented prefix scan — the TPU replacement for the reference's
per-event HashMap group-by (core/query/selector/QuerySelector.java:207,
GroupByKeyGenerator.java:37 string-concat keys + per-key AggregatorState).

Semantics to reproduce: events are processed one at a time; each CURRENT lane
adds its delta to the per-key accumulator and the *post-update* value is
emitted for that lane; EXPIRED lanes subtract (window removal); RESET lanes
zero the accumulator (batch windows). Batched faithfully as:

  1. each lane carries (slot, delta, sign) — slot is a dense int32 key id
  2. lanes are stably sorted by slot; signed deltas are prefix-summed within
     each slot segment; carry-in comes from the persistent state table
  3. results scatter back to original lane order; segment totals update state

Columns that share an index cross a gather packed side by side, and an 8-byte
column crosses a scatter as two 32-bit words (ops/lanes.py: the TPU emulates
64-bit integers, and prices a gather by its index, not by its row).

RESET is handled with *epochs*: a per-key epoch counter increments on reset;
a state-table value whose epoch is stale reads as the aggregator's zero. This
keeps the scan a pure prefix-sum (no data-dependent control flow, XLA-friendly).

All arrays are fixed-shape; invalid lanes carry slot = capacity sentinel so they
sort to the end and never touch real segments.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..telemetry.tracing import stage
from .lanes import gather_lanes, scatter_lanes
from .search import searchsorted32, stable_argsort_bounded


def invert_permutation(perm: jax.Array) -> jax.Array:
    """Inverse of a permutation via scatter — O(n), vs the O(n log n) second
    sort of the argsort(argsort(x)) idiom (slow on TPU)."""
    n = perm.shape[0]
    return jnp.zeros((n,), perm.dtype).at[perm].set(
        jnp.arange(n, dtype=perm.dtype))


class GroupState(NamedTuple):
    """Persistent per-key accumulator table (one per aggregator component).

    values: [K] accumulator per key slot
    epoch:  [K] int32 epoch of last write; values with epoch < current read as 0
    """

    values: jax.Array
    epoch: jax.Array


def init_group_state(capacity: int, dtype) -> GroupState:
    return GroupState(
        values=jnp.zeros((capacity,), dtype=dtype),
        epoch=jnp.zeros((capacity,), dtype=jnp.int32),
    )


def grouped_scan(
    state: GroupState,
    slots: jax.Array,  # int32[L] dense key ids; invalid lanes = any value
    deltas: jax.Array,  # [L] signed per-lane contribution (already sign-applied)
    valid: jax.Array,  # bool[L]
    resets: jax.Array,  # bool[L] lanes that zero their key's accumulator first
    current_epoch: jax.Array,  # int32 scalar epoch counter (increments per reset batch)
    op: str = "sum",  # "sum" | "min" | "max"
) -> tuple[GroupState, jax.Array]:
    """Returns (new_state, per-lane post-update accumulator values).

    `current_epoch` must be >= max(state.epoch); reset lanes bump the epoch of
    *all* keys (batch-window RESET clears every group, matching the reference's
    QuerySelector RESET pass). Keys untouched after a reset read as zero via
    epoch mismatch — no O(K) clear.

    op="min"/"max" support monotone aggregators (no EXPIRED removal — the
    planner forbids min/max over sliding windows until the segment-tree ring
    lands); identity is +/-inf (or dtype extremes for ints).
    """
    K = state.values.shape[0]
    combine, identity = _OPS[op](deltas.dtype)
    plan = _segment_plan(
        slots, valid, resets, current_epoch, K,
        (jnp.where(valid, deltas, jnp.full_like(deltas, identity)),))
    with stage("selector/scan"):
        within = _segmented_scan(plan.s_cols[0], plan.seg_start, combine,
                                 identity)

    # carry-in: only the segment whose epoch matches the state's stored epoch
    # for that slot gets the stored value; stale epochs read the identity.
    with stage("selector/gather"):
        stored_vals, stored_epoch = gather_lanes(
            (state.values, state.epoch), plan.safe_slots)
        carry = jnp.where(
            plan.epoch_ok_slots & (stored_epoch == plan.s_epochs),
            stored_vals, jnp.full_like(stored_vals, identity))
        carry_seg, = gather_lanes((carry,), plan.start_idx)

    with stage("selector/scan"):
        s_out = combine(carry_seg, within)
    with stage("selector/scatter"):
        new_values = scatter_lanes(state.values, plan.write_slot,
                                   s_out.astype(state.values.dtype))
        new_epoch = state.epoch.at[plan.write_slot].set(
            plan.s_epochs.astype(state.epoch.dtype), mode="drop")
        # scatter back to lane order (an inverse permutation and a packed
        # gather cost the same on the chip: PERF.md, PR 33). `order` is a
        # permutation: no two lanes write one place, so an 8-byte column's
        # words may go apart
        out = scatter_lanes(jnp.zeros_like(s_out), plan.order, s_out)
    return GroupState(new_values, new_epoch), out


class _SegmentPlan(NamedTuple):
    """Shared per-batch segment structure: one sort + boundary computation
    reused by every component scanned over the same (slots, valid, resets)."""

    order: jax.Array
    s_slots: jax.Array
    s_epochs: jax.Array
    #: the caller's per-lane columns in sorted order (they ride the one row
    #: gather that sorts the slots and epochs)
    s_cols: tuple
    seg_start: jax.Array
    safe_slots: jax.Array
    epoch_ok_slots: jax.Array  # s_slots < K (validity of gathers)
    write_slot: jax.Array
    #: index of each lane's segment start (shared max-scan — carry
    #: broadcasts become gathers instead of one assoc-scan per component)
    start_idx: jax.Array


def _segment_plan(slots, valid, resets, current_epoch, K,
                  cols: tuple = ()) -> _SegmentPlan:
    sentinel = jnp.int32(K)
    with stage("selector/sort"):
        slots_v = jnp.where(valid, slots, sentinel)

        # epoch id per lane: lanes after the r-th reset belong to epoch
        # current_epoch + r. cumsum of resets gives r per lane (reset lane
        # itself starts the new epoch).
        reset_rank = jnp.cumsum(resets.astype(jnp.int32))
        lane_epoch = current_epoch + reset_rank

        # stable sort by (slot, lane) — lane order inside a slot is preserved.
        # slots_v is non-negative (< K+1), as stable_argsort_bounded requires
        order = stable_argsort_bounded(slots_v)
    with stage("selector/gather"):
        s_slots, s_epochs, *s_cols = gather_lanes(
            (slots_v, lane_epoch, *cols), order)

    with stage("selector/scan"):
        # a new segment starts when slot changes OR lane epoch changes
        prev_slot = jnp.concatenate([jnp.full((1,), -1, s_slots.dtype), s_slots[:-1]])
        prev_epoch = jnp.concatenate([jnp.full((1,), -1, s_epochs.dtype), s_epochs[:-1]])
        seg_start = (s_slots != prev_slot) | (s_epochs != prev_epoch)

        safe_slots = jnp.minimum(s_slots, K - 1)

        # state writes come from the last lane of each *slot* run, every
        # other lane writes the out-of-bounds sentinel and is dropped: NO TWO
        # IN-BOUNDS ENTRIES OF write_slot ARE EQUAL (last epoch's value
        # wins), which is what lets scatter_lanes send an 8-byte table's
        # words apart
        next_slot = jnp.concatenate([s_slots[1:], jnp.full((1,), -1, s_slots.dtype)])
        is_slot_end = s_slots != next_slot
        write_slot = jnp.where((s_slots < K) & is_slot_end, s_slots, sentinel)

        L = s_slots.shape[0]
        idx = jnp.arange(L, dtype=jnp.int32)
        start_idx = jax.lax.associative_scan(
            jnp.maximum, jnp.where(seg_start, idx, 0))

    return _SegmentPlan(order, s_slots, s_epochs, tuple(s_cols), seg_start,
                        safe_slots, s_slots < K, write_slot, start_idx)


def grouped_scan_fused(
    values_list: list,  # per component: [K] accumulator array
    shared_epoch: jax.Array,  # int32[K] — ONE epoch table for all components
    slots: jax.Array,
    deltas_list: list,  # per component: [L] signed deltas
    valid: jax.Array,
    resets: jax.Array,
    current_epoch: jax.Array,
) -> tuple[list, jax.Array, list]:
    """grouped_scan for N sum-op components sharing (slots, valid, resets):
    ONE sort, ONE segment structure, ONE epoch gather/scatter — instead of N
    of each. The dominant per-step HBM traffic for multi-aggregate queries
    (sum+avg = 3 components) drops accordingly. Semantics identical to N
    grouped_scan(op='sum') calls.

    Returns (new_values_list, new_shared_epoch, per-lane outputs list)."""
    K = shared_epoch.shape[0]
    plan = _segment_plan(
        slots, valid, resets, current_epoch, K,
        tuple(jnp.where(valid, d, jnp.zeros((), d.dtype))
              for d in deltas_list))
    with stage("selector/gather"):
        stored_epoch, *stored_vals = gather_lanes(
            (shared_epoch, *values_list), plan.safe_slots)
        epoch_live = plan.epoch_ok_slots & (stored_epoch == plan.s_epochs)
        carries = gather_lanes(
            [jnp.where(epoch_live, sv, jnp.zeros_like(sv))
             for sv in stored_vals], plan.start_idx)
    s_outs = []
    with stage("selector/scan"):
        for values, sd, carry_seg in zip(values_list, plan.s_cols, carries):
            within = _segmented_scan(sd, plan.seg_start, lambda a, b: a + b,
                                     jnp.zeros((), sd.dtype))
            s_outs.append(carry_seg + within.astype(values.dtype))
    with stage("selector/scatter"):
        new_values = [scatter_lanes(values, plan.write_slot, s_out)
                      for values, s_out in zip(values_list, s_outs)]
        new_epoch = shared_epoch.at[plan.write_slot].set(
            plan.s_epochs.astype(shared_epoch.dtype), mode="drop")
        # ONE scatter builds the inverse, one row gather brings every
        # component back to lane order
        back = invert_permutation(plan.order)
    with stage("selector/gather"):
        outs = gather_lanes(s_outs, back)
    return new_values, new_epoch, outs


def ungrouped_scan(
    state: GroupState,
    deltas: jax.Array,
    valid: jax.Array,
    resets: jax.Array,
    current_epoch: jax.Array,
    op: str = "sum",
) -> tuple[GroupState, jax.Array]:
    """`grouped_scan` for the single-group case (no GROUP BY, slots all 0):
    lanes already form one slot run in arrival order, so the sort and the
    permutation scatters vanish — just a segmented scan over reset
    boundaries plus one scalar state cell. Semantics identical to
    grouped_scan with all-zero slots."""
    combine, identity = _OPS[op](deltas.dtype)
    with stage("selector/scan"):
        reset_rank = jnp.cumsum(resets.astype(jnp.int32))
        lane_epoch = current_epoch + reset_rank
        seg_start = jnp.concatenate(
            [jnp.ones((1,), bool), lane_epoch[1:] != lane_epoch[:-1]])
        s_deltas = jnp.where(valid, deltas, jnp.full_like(deltas, identity))
        within = _segmented_scan(s_deltas, seg_start, combine, identity)
        stored = state.values[0]
        # a segment is a run of one lane epoch and the carry depends on
        # nothing else, so every lane already holds its segment's carry: no
        # broadcast from the segment's start (an emulated-int64 gather a step
        # on the TPU)
        carry_seg = jnp.where(state.epoch[0] == lane_epoch, stored,
                              jnp.full_like(stored, identity))
        s_out = combine(carry_seg, within)
    with stage("selector/scatter"):
        new_state = GroupState(
            values=state.values.at[0].set(
                s_out[-1].astype(state.values.dtype)),
            epoch=state.epoch.at[0].set(
                lane_epoch[-1].astype(state.epoch.dtype)))
    return new_state, s_out


def ungrouped_scan_fused(
    values_list: list,
    shared_epoch: jax.Array,
    deltas_list: list,
    valid: jax.Array,
    resets: jax.Array,
    current_epoch: jax.Array,
) -> tuple[list, jax.Array, list]:
    """`grouped_scan_fused` without GROUP BY: shared reset segmentation, no
    sort, scalar state cells."""
    with stage("selector/scan"):
        reset_rank = jnp.cumsum(resets.astype(jnp.int32))
        lane_epoch = current_epoch + reset_rank
        seg_start = jnp.concatenate(
            [jnp.ones((1,), bool), lane_epoch[1:] != lane_epoch[:-1]])
        epoch_ok = shared_epoch[0] == lane_epoch
    new_values, outs = [], []
    for values, deltas in zip(values_list, deltas_list):
        combine, identity = _OPS["sum"](deltas.dtype)
        with stage("selector/scan"):
            s_deltas = jnp.where(valid, deltas,
                                 jnp.full_like(deltas, identity))
            within = _segmented_scan(s_deltas, seg_start, combine, identity)
            # per lane, as in ungrouped_scan: the carry follows the lane epoch
            carry_seg = jnp.where(epoch_ok, values[0],
                                  jnp.full_like(values[0], identity))
            s_out = combine(carry_seg, within)
        with stage("selector/scatter"):
            new_values.append(
                values.at[0].set(s_out[-1].astype(values.dtype)))
        outs.append(s_out)
    with stage("selector/scatter"):
        new_epoch = shared_epoch.at[0].set(lane_epoch[-1].astype(
            shared_epoch.dtype))
    return new_values, new_epoch, outs


def _op_sum(dtype):
    if dtype == jnp.bool_:
        return jnp.logical_or, False
    return jnp.add, jnp.zeros((), dtype)


def _op_min(dtype):
    ident = jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer) else jnp.inf
    return jnp.minimum, jnp.asarray(ident, dtype)


def _op_max(dtype):
    ident = jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer) else -jnp.inf
    return jnp.maximum, jnp.asarray(ident, dtype)


_OPS = {"sum": _op_sum, "min": _op_min, "max": _op_max}


def _segmented_scan(vals: jax.Array, seg_start: jax.Array, combine, identity) -> jax.Array:
    """Inclusive scan that restarts at each segment start (classic conditional
    associative scan: carry a (reset_flag, value) pair)."""

    def op(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, combine(av, bv))

    flags = seg_start
    _, out = jax.lax.associative_scan(op, (flags, vals))
    return out


def _segment_broadcast_op(vals_at_start: jax.Array, seg_start: jax.Array, identity) -> jax.Array:
    """Broadcast each segment-start value across its segment."""
    L = seg_start.shape[0]
    idx = jnp.arange(L)
    start_idx = jnp.where(seg_start, idx, 0)
    start_idx = jax.lax.associative_scan(jnp.maximum, start_idx)
    return vals_at_start[start_idx]


# --- device-side key tables -----------------------------------------------------


class KeyTable(NamedTuple):
    """Append-only device dictionary: 64-bit composite keys → dense int32 ids.

    Replaces the reference's string-concat HashMap group-by keys
    (GroupByKeyGenerator.java:37) for non-string keys, fully on device as an
    **open-addressing hash table**: lookup and insert are a handful of
    int32-addressed gathers plus one conflict-resolving scatter — no sort.
    (The previous sorted-merge design argsorted the whole [K] int64 table
    every step: ~4.8 s/step at K=1M on v5e, where s64 sorting is
    software-emulated.) The hash array has 2x the id capacity, keeping load
    ≤ 50% even at a full id space, so double-hashed probe windows practically
    never exhaust. Ids are dense in [0, count) but assigned in hash-slot
    order per batch, NOT first-appearance order — use DenseKeyTable where
    first-appearance ordering matters.
    """

    keys: jax.Array  # int64[H = 2K]; _KEY_PAD marks an empty slot
    ids: jax.Array  # int32[H] dense id of the key stored at each slot
    count: jax.Array  # int32 number of live keys (ids assigned)
    misses: jax.Array  # int32 lifetime lanes left unresolved (aliased to id 0)


_KEY_PAD = jnp.iinfo(jnp.int64).max

#: probe window per lookup; at ≤50% hash load, P(window exhausted) ≈ α^D —
#: negligible. The 85%-of-K capacity monitors fire long before misses matter.
_PROBE_DEPTH = 16
#: insert retry rounds (each round: probe, claim-by-min scatter, verify).
#: A mass insert of k new keys into H slots loses ~k²/2H first-wave races,
#: shrinking geometrically per wave — 5 claim waves cover a full-batch
#: insert into a small table with negligible residual.
_INSERT_ROUNDS = 6


def init_key_table(capacity: int) -> KeyTable:
    H = 2 * capacity
    return KeyTable(
        keys=jnp.full((H,), _KEY_PAD, dtype=jnp.int64),
        ids=jnp.zeros((H,), dtype=jnp.int32),
        count=jnp.int32(0),
        misses=jnp.int32(0),
    )


def key_lookup_or_insert(
    table: KeyTable, keys: jax.Array, valid: jax.Array
) -> tuple[KeyTable, jax.Array, jax.Array]:
    """Resolve each lane's key to a dense id, inserting unseen keys.

    Returns (new_table, ids[L], resolved[L]). Invalid lanes get id 0 and
    resolved=False. Lanes whose key could not be placed (id space or probe
    window exhausted) also come back unresolved — callers must DROP them
    from downstream scans (monitored truncation via table.misses) rather
    than let them alias id 0.

    Parallel-insert race (two lanes claiming one empty slot) resolves
    deterministically: both scatter with `.min(key)`, the smaller key wins
    (PAD is int64 max, so any key beats an empty slot), losers re-probe with
    their next window slot the following round. Same-key duplicate lanes
    claim the same slot with the same value and all win together.
    """
    L = keys.shape[0]
    H = table.keys.shape[0]
    K = H // 2  # id capacity
    keys = keys.astype(jnp.int64)
    # avoid colliding with the pad sentinel
    keys = jnp.where(keys == _KEY_PAD, _KEY_PAD - 1, keys)

    # probe base + odd stride from the two int32 halves of the key (double
    # hashing kills linear clustering; no emulated s64 math anywhere)
    halves = jax.lax.bitcast_convert_type(keys, jnp.int32)  # [L, 2]
    h32 = (halves[..., 0] ^ halves[..., 1]).astype(jnp.uint32)
    h32 = h32 * jnp.uint32(0x9E3779B9)  # golden-ratio scramble
    base = (h32 % jnp.uint32(H)).astype(jnp.int32)
    stride = (1 + 2 * ((h32 >> 16) & jnp.uint32(7))).astype(jnp.int32)
    probe_off = jnp.arange(_PROBE_DEPTH, dtype=jnp.int32)
    pslots = (base[:, None] + probe_off * stride[:, None]) % H

    def probe(tbl, need, slot_of, wslot, won):
        """One probe round over an int32 view (TPU random gathers are slow;
        a [L,D,2] int32 gather is ~2.5x cheaper than the s64 gather)."""
        pk32 = jax.lax.bitcast_convert_type(tbl, jnp.int32)[pslots]  # [L,D,2]
        match = ((pk32[..., 0] == halves[:, None, 0])
                 & (pk32[..., 1] == halves[:, None, 1]))
        has_match = jnp.any(match, axis=-1)
        midx = jnp.argmax(match, axis=-1)
        mslot = jnp.take_along_axis(pslots, midx[:, None], axis=-1)[:, 0]
        hit = need & has_match
        slot_of = jnp.where(hit, mslot, slot_of)
        # a lane that finds its key at the slot it claimed last round won a
        # new entry (same-key duplicates all win together; deduped later)
        won = won | (hit & (mslot == wslot))
        need = need & ~has_match
        # first empty slot in each window, for the next claim wave
        pad32 = jax.lax.bitcast_convert_type(jnp.int64(_KEY_PAD), jnp.int32)
        empty = (pk32[..., 0] == pad32[0]) & (pk32[..., 1] == pad32[1])
        has_empty = jnp.any(empty, axis=-1)
        eidx = jnp.argmax(empty, axis=-1)
        eslot = jnp.take_along_axis(pslots, eidx[:, None], axis=-1)[:, 0]
        return need, slot_of, won, has_empty, eslot

    slot_of = jnp.zeros((L,), jnp.int32)  # resolved hash slot per lane
    won = jnp.zeros((L,), bool)  # lanes whose claim created a new entry
    wslot = jnp.full((L,), -1, jnp.int32)
    need, slot_of, won, has_empty, eslot = probe(
        table.keys, valid, slot_of, wslot, won)

    def do_insert(args):
        tbl, id_arr, count, need, slot_of, won, has_empty, eslot = args
        wslot = jnp.full((L,), -1, jnp.int32)
        for r in range(_INSERT_ROUNDS - 1):
            claim = need & has_empty
            cand = jnp.where(claim, eslot, H)
            tbl = tbl.at[cand].min(keys, mode="drop")
            wslot = jnp.where(claim, eslot, -1)
            need, slot_of, won, has_empty, eslot = probe(
                tbl, need, slot_of, wslot, won)
        # assign dense ids to the batch's new entries: unique winning slots,
        # ranked in slot order (int32 sort over L lanes — cheap and native)
        ws = jnp.where(won, slot_of, H)
        sw = jnp.sort(ws)
        uniq = (jnp.concatenate([jnp.ones((1,), bool), sw[1:] != sw[:-1]])
                & (sw < H))
        rank = (jnp.cumsum(uniq.astype(jnp.int32)) - 1).astype(jnp.int32)
        new_id = (count + rank).astype(jnp.int32)
        # entries past the id capacity are REVERTED to empty slots (leaving
        # them stored with an aliased id would corrupt group 0 and make dead
        # pairs look live to the compactor); their lanes count as misses via
        # the final verification gather below
        over = uniq & (new_id >= K)
        tbl = tbl.at[jnp.where(over, sw, H)].set(_KEY_PAD, mode="drop")
        keep = uniq & (new_id < K)
        id_arr = id_arr.at[jnp.where(keep, sw, H)].set(new_id, mode="drop")
        n_new = jnp.sum(keep, dtype=jnp.int32)
        return tbl, id_arr, jnp.minimum(count + n_new, jnp.int32(K)), need, \
            slot_of

    def no_insert(args):
        tbl, id_arr, count, need, slot_of = args[:5]
        return tbl, id_arr, count, need, slot_of

    # steady state (every key already present) skips the claim/verify waves
    # entirely — inserts are batch-rare, lookups are every-step
    tbl, id_arr, count, need, slot_of = jax.lax.cond(
        jnp.any(need), do_insert, no_insert,
        (table.keys, table.ids, table.count, need, slot_of, won, has_empty,
         eslot))

    # final verification: a lane is resolved only if its slot still stores
    # its key (overflow reverts and races can undo an apparent win)
    t32 = jax.lax.bitcast_convert_type(tbl, jnp.int32)[slot_of]
    final_ok = (t32[:, 0] == halves[:, 0]) & (t32[:, 1] == halves[:, 1])
    resolved = valid & ~need & final_ok
    ids = jnp.where(resolved, id_arr[slot_of], 0)
    # unresolved lanes alias id 0; the lifetime counter lets runtime monitors
    # surface it (id-space exhaustion or probe-window exhaustion — rare but
    # nonzero even below the 85% capacity thresholds)
    misses = table.misses + jnp.sum(valid & ~resolved, dtype=jnp.int32)
    return (KeyTable(keys=tbl, ids=id_arr, count=count, misses=misses),
            ids, resolved)


class DenseKeyTable(NamedTuple):
    """Sorted-merge key table assigning DENSE ids in first-appearance order
    (the original design). Only for small capacities — inserts argsort the
    whole [K] table, which is emulated-s64-expensive at scale — where
    downstream state is packed per-id (e.g. the sharded-partition slot axis,
    which vmaps over [0, n_slots))."""

    sorted_keys: jax.Array  # int64[K], padded with INT64_MAX
    sorted_ids: jax.Array  # int32[K]
    count: jax.Array  # int32 number of live keys


def init_dense_key_table(capacity: int) -> DenseKeyTable:
    return DenseKeyTable(
        sorted_keys=jnp.full((capacity,), _KEY_PAD, dtype=jnp.int64),
        sorted_ids=jnp.zeros((capacity,), dtype=jnp.int32),
        count=jnp.int32(0),
    )


def dense_key_lookup_or_insert(
    table: DenseKeyTable, keys: jax.Array, valid: jax.Array
) -> tuple[DenseKeyTable, jax.Array]:
    """Resolve each lane's key to a dense id, inserting unseen keys.

    Returns (new_table, ids[L]). Invalid lanes get id 0 (callers mask them).
    Overflow beyond capacity silently reuses id 0 — callers size K generously
    and monitor table.count.
    """
    L = keys.shape[0]
    K = table.sorted_keys.shape[0]
    keys = keys.astype(jnp.int64)
    # avoid colliding with the pad sentinel
    keys = jnp.where(keys == _KEY_PAD, _KEY_PAD - 1, keys)

    pos = searchsorted32(table.sorted_keys, keys)
    pos_c = jnp.clip(pos, 0, K - 1)
    found = table.sorted_keys[pos_c] == keys
    existing_ids = table.sorted_ids[pos_c]

    # identify first occurrence of each new key within the batch, in lane order
    is_new = valid & ~found
    nk = jnp.where(is_new, keys, _KEY_PAD)
    order = jnp.argsort(nk, stable=True)  # groups duplicates, keeps lane order
    snk = nk[order]
    first = jnp.concatenate([jnp.ones((1,), bool), snk[1:] != snk[:-1]]) & (snk != _KEY_PAD)
    # rank new unique keys by first-appearance lane index for deterministic ids
    first_lane = jnp.where(first, order, L)
    lane_rank = invert_permutation(jnp.argsort(first_lane, stable=True))
    new_id_sorted = table.count + lane_rank.astype(jnp.int32)

    # each lane's id: for new keys, find their unique-key id via the sorted run
    run_id = _segment_broadcast_op(
        jnp.where(first, new_id_sorted, 0), first | (snk == _KEY_PAD), 0)
    lane_new_ids = jnp.zeros((L,), jnp.int32).at[order].set(
        jnp.where(snk != _KEY_PAD, run_id, 0).astype(jnp.int32))

    ids = jnp.where(found, existing_ids, lane_new_ids)
    ids = jnp.where(valid, ids, 0)

    # merge new unique keys into the sorted table
    n_new = jnp.sum(first.astype(jnp.int32))
    merged_keys = jnp.concatenate([table.sorted_keys,
                                   jnp.where(first, snk, _KEY_PAD)])
    merged_ids = jnp.concatenate([table.sorted_ids,
                                  jnp.where(first, new_id_sorted, 0)])
    morder = jnp.argsort(merged_keys, stable=True)[:K]
    new_table = DenseKeyTable(
        sorted_keys=merged_keys[morder],
        sorted_ids=merged_ids[morder],
        count=jnp.minimum(table.count + n_new, K),
    )
    return new_table, ids


def hash_columns32(cols: list[jax.Array]) -> jax.Array:
    """32-bit column mix for candidate generation (join probes): all math in
    u32 — the 64-bit variant's u64 multiplies are software-emulated on TPU
    and show up at 100k-row build windows. Collisions only cost re-verified
    candidates, never correctness (callers re-check the exact condition) —
    but the join's multimap takes its bucket from the LOW bits, and a walk
    there is bounded (`join_max_matches` chain entries, matching or not), so
    a lumpy low half loses matches. The FNV round alone put 7 of the dense
    ids 0..100k into one of 2^18 buckets (a uniform hash: 5 at most, once in
    a few tries); murmur3's finalizer after it spreads every input bit over
    every output bit. Bijective, so equality of hashes is what it was."""
    h = jnp.uint32(0x811C9DC5)
    for c in cols:
        if jnp.issubdtype(c.dtype, jnp.floating):
            c = jax.lax.bitcast_convert_type(
                c, jnp.int32 if c.dtype.itemsize == 4 else jnp.int64)
        if c.dtype.itemsize == 8:
            w = jax.lax.bitcast_convert_type(c, jnp.int32)
            words = [w[..., 0], w[..., 1]]
        else:  # 4-byte ints and bool
            words = [c.astype(jnp.int32)]
        for x in words:
            h = (h ^ x.astype(jnp.uint32)) * jnp.uint32(0x01000193)
            h = h ^ (h >> 15)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def hash_columns(cols: list[jax.Array]) -> jax.Array:
    """Combine multiple key columns into one int64 key (fxhash-style mix).
    Collision probability over 64 bits is negligible for CEP key cardinalities.
    Float columns hash by BIT PATTERN (like Java's Double.hashCode), not by
    int truncation — 1.2 and 1.9 are distinct keys."""
    h = jnp.uint64(0xCBF29CE484222325)
    for c in cols:
        if jnp.issubdtype(c.dtype, jnp.floating):
            bits = jax.lax.bitcast_convert_type(
                c, jnp.int32 if c.dtype == jnp.float32 else jnp.int64)
            x = bits.astype(jnp.int64).astype(jnp.uint64)
        else:
            x = c.astype(jnp.int64).astype(jnp.uint64)
        h = (h ^ x) * jnp.uint64(0x100000001B3)
        h = h ^ (h >> 29)
    return h.astype(jnp.int64)


# --- host-side key dictionaries -------------------------------------------------


class KeyDictionary:
    """Host-side composite-key → dense slot assignment for group-by keys that are
    not already dense codes. Append-only; snapshot/restorable. The TPU analogue
    of the reference's group-by key strings: here a key becomes one int32 the
    device can scatter with."""

    def __init__(self) -> None:
        self._map: dict[tuple, int] = {}

    def assign(self, keys) -> "list[int]":
        out = []
        m = self._map
        for k in keys:
            slot = m.get(k)
            if slot is None:
                slot = len(m)
                m[k] = slot
            out.append(slot)
        return out

    def __len__(self) -> int:
        return len(self._map)

    def snapshot(self) -> list:
        return sorted(self._map.items(), key=lambda kv: kv[1])

    def restore(self, items) -> None:
        self._map = {tuple(k) if isinstance(k, list) else k: v for k, v in items}
