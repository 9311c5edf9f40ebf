"""Window operators as pure `(state, batch) -> (state, chunk)` device functions.

Reference counterpart: the 30 WindowProcessor classes under
core/query/processor/stream/window/ that walk per-event linked lists and keep
`SnapshotableStreamEventQueue` heaps. TPU re-design:

- window contents live in **fixed-capacity device ring buffers** (one packed
  u32 word matrix for the sliding, expression and lengthBatch windows; one
  array per column + timestamps for the rest), addressed by monotonically
  growing 64-bit "overall arrival indices" (slot = idx % capacity);
- a step consumes a columnar micro-batch and emits a **chunk**: a wider
  EventBatch whose lanes are typed CURRENT / EXPIRED / RESET and ordered
  exactly as the reference's per-event chunk would interleave them
  (e.g. LengthWindowProcessor.java:118-122 emits [expired, current] per
  arrival; LengthBatchWindowProcessor.java:210-243 emits
  [expired(prev flush), RESET, current(flush)] at each flush boundary);
- ordering is produced by a rank merge (or a stable sort) on an emission
  key — or, where the interleave is fixed (lengthBatch), by arithmetic on the
  lane number — so the whole window step is one fused XLA program with
  static shapes.

The downstream selector consumes chunks with signed-delta grouped scans
(ops/groupby.py), reproducing per-event aggregate semantics.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core import dtypes
from .search import rank_sorted32, searchsorted32, stable_partition_order
from ..core.event import EventBatch, EventType
from ..errors import SiddhiAppCreationError
from ..telemetry.tracing import stage

# emission-key kinds: expired lanes sort before reset before current at the
# same trigger position (matches reference chunk insertion order).
KIND_EXPIRED = 0
KIND_RESET = 1
KIND_CURRENT = 2

# Python int, NOT a jnp scalar: a device-resident constant captured by a jit
# closure was uploaded again on every execution in rounds 3–4 (measured on
# another runner; to re-check on the chip, docs/PERFORMANCE.md) — literals
# trace into the HLO for free.
BIG = 2**62


# --------------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------------- #


def compact(batch: EventBatch) -> tuple[dict, jax.Array, jax.Array, jax.Array]:
    """Stable-partition valid CURRENT lanes to the front.

    Returns (cols, ts, n_valid, order). Lanes >= n_valid hold garbage.
    """
    live = batch.valid & (batch.types == EventType.CURRENT)
    order = stable_partition_order(live)
    cols = {k: v[order] for k, v in batch.cols.items()}
    ts = batch.ts[order]
    return cols, ts, jnp.sum(live.astype(jnp.int32)), order


def _gather_overall(
    ring_cols: dict,
    ring_ts: jax.Array,
    comp_cols: dict,
    comp_ts: jax.Array,
    appended0: jax.Array,
    o_idx: jax.Array,
):
    """Fetch events by overall arrival index: from the ring for pre-batch
    events, from the compacted batch for this batch's arrivals.

    NOTE: vectorized int64 `%`/`-` here is software-emulated on TPU (no
    native s64 ALU) — hot windows use `_gather_rel` instead, which keeps the
    per-lane math in int32 and only the scalar base in int64."""
    C = ring_ts.shape[0]
    B = comp_ts.shape[0]
    from_batch = o_idx >= appended0
    ring_slot = jnp.clip(o_idx, 0, None) % C
    batch_slot = jnp.clip(o_idx - appended0, 0, B - 1)
    cols = {
        k: jnp.where(from_batch, comp_cols[k][batch_slot], ring_cols[k][ring_slot])
        for k in ring_cols
    }
    ts = jnp.where(from_batch, comp_ts[batch_slot], ring_ts[ring_slot])
    return cols, ts


def _gather_rel(ring_cols, ring_ts, comp_cols, comp_ts, appended0, base, offs):
    """`_gather_overall` for o_idx = base + offs, with ALL per-lane arithmetic
    in int32: `base` is an int64 scalar (folded into two scalar reductions),
    `offs` an int32 vector. TPU v5e has no native s64 ALU — per-lane s64
    div/mod lowers to thousands of emulated ops — so the hot windows keep
    lane math 32-bit and reserve int64 for scalars and timestamp payloads."""
    C = ring_ts.shape[0]
    B = comp_ts.shape[0]
    # offset of the first batch arrival relative to base (|value| <= C+B)
    rel0 = (appended0 - base).astype(jnp.int32)
    from_batch = offs >= rel0
    batch_slot = jnp.clip(offs - rel0, 0, B - 1)
    # floored modulo wraps a negative base correctly (callers mask lanes
    # whose overall index is negative, but lanes at base+offs >= 0 with a
    # negative base are real ring rows and must hit their true slot)
    ring_base = (base % C).astype(jnp.int32)
    ring_slot = (ring_base + offs) % C
    cols = {
        k: jnp.where(from_batch, comp_cols[k][batch_slot], ring_cols[k][ring_slot])
        for k in ring_cols
    }
    ts = jnp.where(from_batch, comp_ts[batch_slot], ring_ts[ring_slot])
    return cols, ts


def _scatter_append(ring_cols, ring_ts, comp_cols, comp_ts, appended0, n_valid):
    """Write the batch's valid events into the ring at slot (appended0+p)%C.
    When more than C events arrive in one batch only the last C survive —
    earlier lanes are masked out so the scatter has no duplicate slots.
    Per-lane math is int32 (see `_gather_rel`)."""
    C = ring_ts.shape[0]
    B = comp_ts.shape[0]
    p = jnp.arange(B, dtype=jnp.int32)
    n_valid = n_valid.astype(jnp.int32)
    keep = (p < n_valid) & (p >= n_valid - C)
    base = (appended0 % C).astype(jnp.int32)
    slot = jnp.where(keep, (base + p) % C, C)  # C = drop sentinel
    new_cols = {k: ring_cols[k].at[slot].set(comp_cols[k], mode="drop")
                for k in ring_cols}
    new_ts = ring_ts.at[slot].set(comp_ts, mode="drop")
    return new_cols, new_ts


def _sort_chunk(keys, cols, ts, valid, types, width):
    """Order lanes by emission key (invalid lanes pushed to the end) and trim
    to `width` lanes.

    `keys` is either an int32 (hi, lo) pair — the fast path, sorted with a
    native two-key 32-bit comparator — or a single legacy array (extra
    windows; s64 keys sort via emulated two-word compares there)."""
    if isinstance(keys, tuple):
        hi, lo = keys
        hi = jnp.where(valid, hi, jnp.iinfo(jnp.int32).max)
        iota = jnp.arange(hi.shape[0], dtype=jnp.int32)
        _, _, order = jax.lax.sort((hi, lo, iota), num_keys=2, is_stable=True)
        order = order[:width]
    else:
        k = jnp.where(valid, keys, BIG)
        order = jnp.argsort(k, stable=True)[:width]
    return EventBatch(
        ts=ts[order],
        cols={n: v[order] for n, v in cols.items()},
        valid=valid[order],
        types=types[order],
    )


def _merge_order(keys, valids):
    """Global emission permutation for G lane groups whose VALID lanes
    already carry nondecreasing (hi, lo) keys — true for every window-chunk
    assembly that merges by key: expression, timeBatch and the windows of
    windows_extra.py (currents/RESETs/expireds are generated in emission
    order). lengthBatch needs no keys (its interleave is fixed), and
    SlidingWindow none either: it counts its ranks against running maxima.

    INVARIANT (monotone-timestamp ingress): each group's valid-lane keys
    must be nondecreasing in lane order. Window emission keys derive from
    event timestamps/arrival order, and every ingress path guarantees
    monotone timestamps per junction (flush pads ts with the last value;
    the watermark never regresses — core/stream.py). Feeding a window
    out-of-order timestamps (e.g. externalTime over a disordered attribute
    clock) breaks the premise: the rank-merge scatter would silently
    drop/duplicate lanes where a comparator sort merely mis-ordered output.
    With `dtypes.config.debug_checks` (or SIDDHI_DEBUG_CHECKS=1) each
    group's key order is verified per step and violations warn loudly.

    Replaces the chunk comparator sort (XLA CPU: ~74 ms at 282k lanes) with
    per-group stable partitions + cross-group searchsorted rank sums
    (~2 ms): merged_rank(lane) = local_rank + Σ_h |{k in group h : k < key}|
    (≤ for groups ordered earlier, < for later — reproducing the stable
    concatenation order on ties). TPU also wins: no bitonic sort pass.
    Returns order over the CONCATENATED index space (valid lanes first, in
    key order; invalid lanes after, in concatenation order)."""
    G = len(keys)
    lens = [k[0].shape[0] for k in keys]
    total = sum(lens)
    offsets = [sum(lens[:g]) for g in range(G)]

    ck, orders, nvs = [], [], []
    for (hi, lo), v in zip(keys, valids):
        og = stable_partition_order(v)
        nv = jnp.sum(v.astype(jnp.int32))
        k = (hi.astype(jnp.int64) << 32) | lo.astype(jnp.uint32).astype(jnp.int64)
        k = k[og]
        k = jnp.where(jnp.arange(k.shape[0]) < nv, k, jnp.int64(BIG))
        ck.append(k)
        orders.append(og)
        nvs.append(nv)
    total_valid = sum(nvs)

    if dtypes.config.debug_checks:
        # partitioned keys end with a BIG suffix, so one pairwise compare
        # per group covers exactly the valid prefix
        ok = jnp.bool_(True)
        for k in ck:
            if k.shape[0] > 1:
                ok = ok & jnp.all(k[1:] >= k[:-1])
        jax.debug.callback(_warn_nonmonotone_keys, ok)

    order_all = jnp.zeros((total,), jnp.int32)
    inv_base = total_valid
    for g in range(G):
        iota = jnp.arange(lens[g], dtype=jnp.int32)
        rank = iota
        for h in range(G):
            if h == g:
                continue
            side = "right" if h < g else "left"
            rank = rank + searchsorted32(ck[h], ck[g], side=side)
        is_val = iota < nvs[g]
        rank = jnp.where(is_val, rank, inv_base + (iota - nvs[g]))
        inv_base = inv_base + (lens[g] - nvs[g])
        order_all = order_all.at[rank].set(offsets[g] + orders[g])
    return order_all


def _warn_nonmonotone_keys(ok) -> None:
    """Debug-checks callback: fires host-side per step (see _merge_order)."""
    if not bool(ok):
        import warnings
        warnings.warn(
            "window rank-merge received a group whose valid-lane emission "
            "keys are NOT nondecreasing — the monotone-timestamp ingress "
            "invariant is broken (out-of-order event/attribute clocks?); "
            "the scatter may drop or duplicate lanes. Fix the ingress "
            "ordering (docs/PARITY.md 'monotone-timestamp invariant')",
            stacklevel=2)


def _merge_sorted_chunks(keys, colss, tss, valids, types, width):
    """Rank-merged chunk assembly (see `_merge_order`)."""
    order = _merge_order(keys, valids)[:width]
    all_cols = {k: jnp.concatenate([c[k] for c in colss]) for k in colss[0]}
    all_ts = jnp.concatenate(tss)
    all_valid = jnp.concatenate(valids)
    all_types = jnp.concatenate(types)
    return EventBatch(
        ts=all_ts[order],
        cols={n: v[order] for n, v in all_cols.items()},
        valid=all_valid[order],
        types=all_types[order],
    )


def _empty_like_cols(layout: dict, n: int) -> dict:
    return {k: jnp.zeros((n,), dtype=dt) for k, dt in layout.items()}


class TypedLayout(dict):
    """Column layout (name -> device dtype) carrying the AttributeTypes
    behind it, for window factories that must distinguish STRING codes from
    raw ints (both int32 on device). Build via `make_layout`."""

    attr_types: dict


def make_layout(attr_types: dict) -> TypedLayout:
    """attr_types: name -> AttributeType (OBJECT already excluded)."""
    from ..core import dtypes as _dt
    lo = TypedLayout({n: _dt.device_dtype(t) for n, t in attr_types.items()})
    lo.attr_types = dict(attr_types)
    return lo


# --------------------------------------------------------------------------- #
# packed-row payload: all columns + ts as one u32 matrix
#
# TPU per-op overhead dominates these steps (profiled ~0.1 ms per gather/
# scatter fusion at 8-16k lanes); per-column rings cost one memory op per
# column per phase. Packing every column into one [*, W] u32 matrix makes
# ring append, candidate fetch, and the emission-sort gather ONE memory op
# each, independent of column count. 8-byte payloads (int64/f64 + ts) span
# two words; bitcasts/stacks fuse into neighbouring elementwise work.
#
# Users: SlidingWindow and LengthBatchWindow here, ExpressionWindow
# (expression_window.py), GeneralExpressionWindow and
# GeneralExpressionBatchWindow (expression_general.py). TimeBatchWindow,
# SessionWindow, SortWindow and windows_extra.py still keep one ring array per
# column (compact / _gather_rel / _scatter_append / _merge_sorted_chunks).
# --------------------------------------------------------------------------- #


def _layout_words(layout: dict) -> int:
    """u32 words per packed row: columns in layout order, then 2 ts words."""
    n = 0
    for dt in layout.values():
        n += 1 if (jnp.dtype(dt) == jnp.bool_
                   or jnp.dtype(dt).itemsize == 4) else 2
    return n + 2


def _pack_rows(cols: dict, ts: jax.Array, layout: dict) -> jax.Array:
    """Pack columns + ts into a [W, L] u32 word matrix.

    TPU layout note: the LANE (minor) axis must be the long row axis — a
    [L, W] matrix with W ~ 4-8 pads the minor dim to 128 lanes, physically
    inflating a 100k-row ring ~20-30x and turning every ring copy into a
    multi-ms HBM burn. [W, L] keeps lanes fully packed."""
    words = []
    for name, dt in layout.items():
        a = cols[name]
        if a.dtype == jnp.bool_:
            words.append(a.astype(jnp.uint32))
        elif a.dtype.itemsize == 8:
            w = jax.lax.bitcast_convert_type(a, jnp.uint32)
            words.append(w[..., 0])
            words.append(w[..., 1])
        else:
            words.append(jax.lax.bitcast_convert_type(a, jnp.uint32))
    w = jax.lax.bitcast_convert_type(ts.astype(jnp.int64), jnp.uint32)
    words.append(w[..., 0])
    words.append(w[..., 1])
    return jnp.stack(words, axis=0)  # [W, L]


def _unpack_rows(mat: jax.Array, layout: dict) -> tuple[dict, jax.Array]:
    cols = {}
    i = 0
    for name, dt in layout.items():
        dt = jnp.dtype(dt)
        if dt == jnp.bool_:
            cols[name] = mat[i] != 0
            i += 1
        elif dt.itemsize == 8:
            cols[name] = jax.lax.bitcast_convert_type(
                jnp.stack([mat[i], mat[i + 1]], axis=-1), dt)
            i += 2
        else:
            cols[name] = jax.lax.bitcast_convert_type(mat[i], dt)
            i += 1
    ts = jax.lax.bitcast_convert_type(
        jnp.stack([mat[i], mat[i + 1]], axis=-1), jnp.int64)
    return cols, ts


def _packed_ts(mat: jax.Array) -> jax.Array:
    """The ts payload (last two words) of packed rows, as int64."""
    return jax.lax.bitcast_convert_type(
        jnp.stack([mat[-2], mat[-1]], axis=-1), jnp.int64)


def compact_packed(batch: EventBatch, layout: dict):
    """compact() producing one packed matrix: returns (mat[W,B], n_valid32).
    Lanes >= n_valid hold garbage."""
    live = batch.valid & (batch.types == EventType.CURRENT)
    mat = _pack_rows(batch.cols, batch.ts, layout)
    order = stable_partition_order(live)
    return mat[:, order], jnp.sum(live, dtype=jnp.int32)


def _ring_lanes(ring: jax.Array, base, n: int):
    """Ring lanes (base + [0, n)) % C, n <= C, touching 2n lanes and never
    the whole ring. XLA clamps a dynamic slice's origin so that it fits: the
    slice at `base` then starts `shift` lanes early, and as many of the
    wanted lanes lie at the ring's head, so the wanted lanes are a second
    slice, at `shift`, of that one beside the ring's first n lanes. Returns
    them, and for a writer the clamped origin and `shift`."""
    W, C = ring.shape
    start = jnp.minimum(base, C - n)
    shift = base - start
    body = jax.lax.dynamic_slice(ring, (jnp.int32(0), start), (W, n))
    pair = jnp.concatenate([body, ring[:, :n]], axis=1)
    lanes = jax.lax.dynamic_slice(pair, (jnp.int32(0), shift), (W, n))
    return lanes, body, start, shift


def _append_packed(ring: jax.Array, comp_mat: jax.Array, appended0,
                   n_valid32) -> jax.Array:
    """Contiguous FIFO append of comp_mat[:, :n_valid] at ring lane
    appended0%C. Requires B <= C (callers size rings accordingly). No
    scatter and no copy of the ring: the B lanes at the write position are
    read, blended and written back by two dynamic-update-slices (the run up
    to the ring's end, then the wrapped rest at its head), so a donated
    ring is updated in place and the step's cost follows B, never C."""
    W, C = ring.shape
    B = comp_mat.shape[1]
    base = (appended0 % C).astype(jnp.int32)
    p = jnp.arange(B, dtype=jnp.int32)
    old, body, start, shift = _ring_lanes(ring, base, B)
    blend = jnp.where((p < n_valid32)[None, :], comp_mat, old)
    # an update's origin is clamped like a slice's: the block written at
    # `start` keeps `body`'s first `shift` lanes and takes the batch from
    # there on, rolled[k] = blend[(k - shift) % B]; the batch's last `shift`
    # lanes wrap to the head
    rolled = jax.lax.dynamic_slice(
        jnp.concatenate([blend, blend], axis=1),
        (jnp.int32(0), B - shift), (W, B))
    wrapped = (p < shift)[None, :]
    ring = jax.lax.dynamic_update_slice(
        ring, jnp.where(wrapped, body, rolled), (jnp.int32(0), start))
    # the head is read AFTER the first write: on a ring under 2B lanes the
    # two regions overlap, and the head's unwrapped lanes must keep it
    return jax.lax.dynamic_update_slice(
        ring, jnp.where(wrapped, rolled, ring[:, :B]),
        (jnp.int32(0), jnp.int32(0)))


def _fetch_rel_packed(ring: jax.Array, comp_mat: jax.Array, base_idx,
                      appended0, E: int) -> jax.Array:
    """Rows at overall indices base_idx + [0, E): from the ring for pre-batch
    rows, from the compacted batch for this batch's arrivals. Contiguous:
    dynamic slices of E lanes + one blend (the packed `_gather_rel`)."""
    W, C = ring.shape
    B = comp_mat.shape[1]
    cand = _ring_lanes(ring, (base_idx % C).astype(jnp.int32), E)[0]
    rel0 = (appended0 - base_idx).astype(jnp.int32)  # first batch offset
    # align batch lanes so slice lane i reads comp_mat[:, i - rel0]. The
    # slice origin E - rel0 ranges over [0, E] (rel0 >= 0), so the padded
    # array needs 2E lanes: E leading zeros + comp + trailing zeros. Lanes
    # past the real batch read zeros but are masked by callers
    # (cand_exists), since pe >= rel0 + n_valid is beyond the window's end.
    pad_tail = max(E - B, 0)
    padded = jnp.concatenate(
        [jnp.zeros((W, E), jnp.uint32), comp_mat,
         jnp.zeros((W, pad_tail), jnp.uint32)], axis=1)
    start = jnp.clip(E - rel0, 0, E)
    bat = jax.lax.dynamic_slice(padded, (jnp.int32(0), start), (W, E))
    offs = jnp.arange(E, dtype=jnp.int32)
    return jnp.where((offs >= rel0)[None, :], bat, cand)


def _gather_chunk_packed(order, payload_mat, emit_ts, valid, types,
                         layout: dict) -> EventBatch:
    """Apply an emission permutation with ONE packed gather: payload +
    emit ts + (valid, type) meta ride a single [W+3, L] matrix."""
    ets = jax.lax.bitcast_convert_type(emit_ts.astype(jnp.int64), jnp.uint32)
    meta = (valid.astype(jnp.uint32)
            | (types.astype(jnp.uint32) << 1))
    W = payload_mat.shape[0]
    full = jnp.concatenate(
        [payload_mat, ets.T, meta[None, :]], axis=0)[:, order]
    cols, _stored_ts = _unpack_rows(full[:W], layout)
    emit = jax.lax.bitcast_convert_type(
        jnp.stack([full[W], full[W + 1]], axis=-1), jnp.int64)
    m = full[W + 2]
    return EventBatch(ts=emit, cols=cols,
                      valid=(m & 1) != 0,
                      types=(m >> 1).astype(jnp.int8))


def _sort_chunk_packed(hi, lo, payload_mat, emit_ts, valid, types,
                       layout: dict, width: int) -> EventBatch:
    """Emission-order sort (general, comparator-based) + packed gather.
    Window paths whose groups emit in key order use `_merge_order` +
    `_gather_chunk_packed` instead."""
    L = hi.shape[0]
    hi = jnp.where(valid, hi, jnp.iinfo(jnp.int32).max)
    iota = jnp.arange(L, dtype=jnp.int32)
    _, _, order = jax.lax.sort((hi, lo, iota), num_keys=2, is_stable=True)
    return _gather_chunk_packed(order[:width], payload_mat, emit_ts, valid,
                                types, layout)


def window_has_time_semantics(window: "WindowOp") -> bool:
    """True if the window needs heartbeats (empty timer batches) to emit
    expirations when no data arrives — the TPU analogue of the reference's
    Scheduler TIMER wiring (core/util/Scheduler.java:48)."""
    if getattr(window, "time_ms", None) is not None:
        return True
    if getattr(window, "needs_heartbeat", False):  # cron/hopping etc.
        return True
    return isinstance(window, (TimeBatchWindow, SessionWindow))


class WindowOp:
    """Base window operator. Subclasses define init_state/step; both must be
    traceable (called inside the query's jitted step)."""

    #: chunk width produced per step for a FULL-capacity batch (static upper
    #: bound — rate limiters size their rings from it)
    chunk_width: int
    #: True when step() derives the lane count from the incoming batch
    #: instead of the planned batch capacity — the window then accepts
    #: shape-bucketed (narrower) batches directly; runtimes pad batches
    #: back to full capacity for windows that bake their B
    shape_polymorphic = False
    #: the app runs under @app:playback (set where the window is built,
    #: before the first trace): time comes from the events, not the wall
    playback = False

    def init_state(self):
        raise NotImplementedError

    def resize(self, window: Optional[int], expire: Optional[int]) -> None:
        """@capacity(window=, expire=): only a window with such a ring."""
        raise SiddhiAppCreationError(
            f"@capacity(window=..., expire=...) sizes the ring of a sliding "
            f"window (time, delay, externalTime; expire also length and "
            f"timeLength); {type(self).__name__} has none")

    def step(self, state, batch: EventBatch, now: jax.Array):
        raise NotImplementedError

    def contents(self, state, now: jax.Array):
        """Current in-window rows as (cols, ts, valid) over the ring — the
        FindableProcessor surface joins probe (reference:
        core/query/processor/stream/window/SlidingFindableWindowProcessor).
        Base: no findable contents."""
        raise SiddhiAppCreationError(
            f"window {type(self).__name__} is not findable (joins)")


def _ring_live_mask(ring_len: int, lo: jax.Array, hi: jax.Array):
    """Valid-slot mask for a ring holding overall indices [lo, hi): slot s's
    most recent write is idx = hi-1 - ((hi-1-s) % C); it is live iff >= lo."""
    s = jnp.arange(ring_len, dtype=jnp.int64)
    last_written = hi - 1 - ((hi - 1 - s) % ring_len)
    return (last_written >= 0) & (last_written >= lo) & (last_written < hi)


# --------------------------------------------------------------------------- #
# sliding windows (length, time, timeLength, delay)
# --------------------------------------------------------------------------- #


class SlidingState(NamedTuple):
    ring: jax.Array  # u32[W, C] packed rows (all columns + ts words)
    appended: jax.Array  # int64 total valid arrivals ever
    expired: jax.Array  # int64 total expirations ever
    wm: jax.Array  # int64 the window's clock after the last step (time modes)
    overflow: jax.Array  # int64 lifetime live rows overwritten past capacity
    deferred: jax.Array  # int64 lifetime rows that left late: E ran out
    live_hwm: jax.Array  # int64 most live rows after a step, since a report


def sliding_state0(words: int, capacity: int) -> SlidingState:
    return SlidingState(
        ring=jnp.zeros((words, capacity), jnp.uint32),
        appended=jnp.int64(0),
        expired=jnp.int64(0),
        wm=jnp.int64(-(2**62)),
        overflow=jnp.int64(0),
        deferred=jnp.int64(0),
        live_hwm=jnp.int64(0),
    )


def _cummax(x: jax.Array) -> jax.Array:
    # a log-depth scan: `lax.cummax` of an int64 takes minutes to compile
    # for the TPU (PERF.md, PR 30)
    return jax.lax.associative_scan(jnp.maximum, x)


class SlidingWindow(WindowOp):
    """Unified FIFO sliding window: length(N) and time(W) (and timeLength) are
    the same machine with different expiry rules. Events leave strictly in
    arrival order, so the window is always a contiguous [expired, appended)
    range of overall indices.

    Reference: LengthWindowProcessor.java:105-143, TimeWindowProcessor.java:133.
    The time rule is upstream's FIFO walk, whatever the stamps' order: per
    arriving event the clock moves to the running maximum of the stamps, the
    head is popped while `head.ts + W <= clock` (the walk stops at the first
    head that is not due: a row behind it waits, however old), then the
    arrival is appended. Which clock: under @app:playback (`playback`, set
    by the runtime) and for externalTime the running maximum of the stamps
    THIS WINDOW has processed, carried in the state — never the app's clock
    as some other thread observed it, which on the served path runs frames
    ahead of the batch — and a timer batch's `now`; on the wall clock the
    stamps of the batch, then `now` (scheduler-driven TIMER expiry becomes
    watermark-driven: heartbeats flush due expirations). docs/PARITY.md.
    """

    shape_polymorphic = True  # step() reads B from the batch (bucketing)

    def __init__(self, layout: dict, batch_cap: int, *,
                 length: Optional[int] = None,
                 time_ms: Optional[int] = None,
                 capacity: Optional[int] = None,
                 max_expired: Optional[int] = None,
                 is_delay: bool = False,
                 ts_attr: Optional[str] = None):
        self.layout = layout
        self.B = batch_cap
        self.length = length
        self.time_ms = time_ms
        self.is_delay = is_delay
        #: externalTime(tsAttr, W): expiry driven by an event attribute clock
        #: (reference: ExternalTimeWindowProcessor) instead of arrival time
        self.ts_attr = ts_attr
        #: @app:eventTime allowed lateness (set by the query runtime): the
        #: device watermark trails max-seen by this much so panes stay open
        #: for rows the ingress gate still buffers. Static Python attr — the
        #: default 0 keeps the traced jaxpr identical to the pre-lateness
        #: form (optimizer parity + SL204 fastpath certification)
        self.lateness_ms = 0
        self.W = _layout_words(layout)
        self._capacity = capacity
        self._size(capacity, max_expired)

    def _size(self, capacity: Optional[int], max_expired: Optional[int]):
        """Ring capacity C and expiry width E (rows that may leave a step)."""
        counted = self.length is not None and self.time_ms is None
        # packed FIFO appends require B <= C (no last-C overwrite dance)
        if counted:
            self.C = max(self.length, self.B, 1)
        else:
            self.C = max(capacity or dtypes.config.default_window_capacity,
                         self.B)
        self.E = max_expired if max_expired is not None else (
            self.B if counted else max(self.B, 1024))
        # the packed candidate fetch slices E rows of the ring — a ring
        # smaller than E (tiny timeLength counts) cannot hold them
        self.C = max(self.C, self.E)
        self.chunk_width = self.B + self.E

    def resize(self, window: Optional[int], expire: Optional[int]) -> None:
        """@capacity(window=, expire=) on the query: the ring's rows and the
        rows that may leave it in one step, as the deployed app states them
        (the defaults are sized for a test, not for a minute of traffic).
        Before the first trace; refuses what cannot hold."""
        if window is not None:
            if self.length is not None:
                raise SiddhiAppCreationError(
                    f"@capacity(window={window}): the ring of a length or "
                    f"timeLength window holds its count, {self.length} rows;"
                    " only a window bounded by time alone takes a capacity")
            if window < self.B:
                raise SiddhiAppCreationError(
                    f"@capacity(window={window}): a window's ring holds at "
                    f"least one batch, {self.B} rows")
        if expire is not None and expire > max(window or self.C, self.B):
            raise SiddhiAppCreationError(
                f"@capacity(expire={expire}): more rows than the ring's "
                f"{window or self.C} cannot leave it in one step")
        self._size(window if window is not None else self._capacity,
                   expire)
        stats = jax.devices()[0].memory_stats() or {}
        limit = int(stats.get("bytes_limit", 16 << 30))
        ring = 4 * self.W * self.C
        if ring > limit:
            raise SiddhiAppCreationError(
                f"@capacity(window={self.C}): a ring of {self.C:,} rows of "
                f"{self.W} words is {ring:,} bytes, more than the device's "
                f"{limit:,}")

    def init_state(self) -> SlidingState:
        return sliding_state0(self.W, self.C)

    def step(self, state: SlidingState, batch: EventBatch, now: jax.Array):
        # B is the INCOMING batch capacity (<= self.B under shape-bucketed
        # dispatch): every lane-count shape below derives from it, so one
        # window instance serves the whole bucket ladder (one trace per rung)
        B, E = batch.capacity, self.E
        with stage("window/append"):
            comp_mat, n_valid32 = compact_packed(batch, self.layout)
        n_valid = n_valid32.astype(jnp.int64)

        if self.ts_attr is not None:
            # external clock: the time axis is an event attribute. The packed
            # ts words are REPLACED by the attribute clock so ring rows carry
            # the expiry-relevant time.
            tcols, _ = _unpack_rows(comp_mat, self.layout)
            comp_ts = tcols[self.ts_attr].astype(jnp.int64)
            w = jax.lax.bitcast_convert_type(comp_ts, jnp.uint32)
            comp_mat = comp_mat.at[-2].set(w[..., 0]).at[-1].set(w[..., 1])
        else:
            comp_ts = _packed_ts(comp_mat)

        appended1 = state.appended + n_valid
        p = jnp.arange(B, dtype=jnp.int32)
        cur_valid = p < n_valid32

        # ---- expiry candidates: the E oldest in-window events ----
        # One contiguous packed fetch (ring rows blended with batch rows);
        # per-lane index math stays int32 (s64 lane math is emulated on TPU).
        pe = jnp.arange(E, dtype=jnp.int32)
        win_len0 = (state.appended - state.expired).astype(jnp.int32)
        win_len1 = win_len0 + n_valid32
        with stage("window/fetch"):
            cand_mat = _fetch_rel_packed(
                state.ring, comp_mat, state.expired, state.appended, E)

        with stage("window/expire"):
            # Every rule below says how many candidates have left, in FIFO
            # order, by the time arrival i is appended (`pops`, nondecreasing in
            # i) and by the end of the step (`n_exp`); the rest is shared.
            wm, deferred = state.wm, state.deferred
            if self.time_ms is not None:
                # a candidate leaves at the first arrival whose clock reaches the
                # running maximum of the deadlines up to it, before that arrival
                # is counted (ties: expire first); never before it has arrived
                # itself; at the step's end if only the final clock covers it.
                own = _packed_ts(cand_mat) + jnp.int64(self.time_ms)
                deadline = _cummax(jnp.where(pe < win_len1, own, BIG))
                lane_clock = _cummax(jnp.where(cur_valid, comp_ts, -BIG))
                tracked = self.ts_attr is not None or self.playback
                if tracked:
                    lane_clock = jnp.maximum(lane_clock, state.wm)
                    # the allowed lateness holds the step's last clock back, so
                    # that panes close only once the ingress gate can release no
                    # more rows into them; a timer batch brings its own clock
                    end = lane_clock[-1] - jnp.int64(self.lateness_ms)
                    if self.ts_attr is None:
                        end = jnp.where(n_valid32 > 0, end, now)
                    end = jnp.maximum(state.wm, end)
                else:
                    end = now
                n_time = jnp.minimum(jnp.sum(deadline <= end, dtype=jnp.int32),
                                     win_len1)
                if tracked and self.ts_attr is None:
                    # a data batch under playback ends with its last arrival's
                    # own pops, and that arrival stays: upstream looks at the
                    # head again only at the next event
                    n_time = jnp.minimum(
                        n_time, win_len1 - (n_valid32 > 0).astype(jnp.int32))
                # both are running maxima, so sorted: a merge, not a search
                pops_time = jnp.minimum(
                    rank_sorted32(deadline, lane_clock, side="right"),
                    jnp.minimum(win_len0 + p, n_time))
                # rows the step before should have let go and could not: E ran
                # out (its newest row may stand, as above)
                deferred = deferred + jnp.sum(
                    (deadline <= state.wm) & (pe < win_len0 - 1), dtype=jnp.int64)
                wm = jnp.maximum(state.wm, end)
            if self.length is not None:
                # length(N): candidate o is evicted by the arrival with overall
                # index o + N (the N+1'th event), all relative -> int32
                rel = (state.expired + jnp.int64(self.length)
                       - state.appended).astype(jnp.int32)
                pops_len = jnp.clip(p + 1 - rel, 0, jnp.minimum(E, win_len1))
                n_len = jnp.clip(n_valid32 - rel, 0, jnp.minimum(E, win_len1))
            if self.time_ms is None:
                pops, n_exp = pops_len, n_len
            elif self.length is None:
                pops, n_exp = pops_time, n_time
            else:
                # timeLength(W, N): whichever rule fires first
                pops = jnp.maximum(pops_time, pops_len)
                n_exp = jnp.maximum(n_time, n_len)
            pops = jnp.where(cur_valid, jnp.minimum(pops, n_exp), n_exp)
            expires = pe < n_exp
            # trigger position of candidate j: the arrivals that did not see it
            # leave, counted by a histogram of `pops` (sorted indices) and a
            # prefix sum; n_valid where only the step's end lets it go
            trig = jnp.cumsum(jnp.zeros((E + 1,), jnp.int32).at[pops].add(
                1, indices_are_sorted=True))[:E]
            trig = jnp.minimum(trig, n_valid32)

            # stamps of the expired lanes
            safe_trig = jnp.clip(trig, 0, B - 1)
            if self.time_ms is None:
                # reference stamps evicted events with current time
                # (LengthWindowProcessor.java:121)
                emit_ts = comp_ts[safe_trig]
            elif self.length is None:
                emit_ts = own
            else:
                by_time = (trig >= n_valid32) | (pops_time[safe_trig] > pe)
                emit_ts = jnp.where(by_time, own, comp_ts[safe_trig])

            # ---- assemble chunk: E expired lanes + B current lanes ----
            # emission order: candidate j goes out before the arrival that sees
            # it leave, so its rank is j + trig[j] and arrival i's is i + pops[i];
            # lanes that emit nothing follow in concatenation order
            all_mat = jnp.concatenate([cand_mat, comp_mat], axis=1)
            all_emit = jnp.concatenate([emit_ts, comp_ts])
            all_types = jnp.concatenate([
                jnp.full((E,), EventType.EXPIRED, jnp.int8),
                jnp.full((B,), EventType.CURRENT, jnp.int8),
            ])
            if self.is_delay:
                # delay(W): expired lanes are re-emitted as CURRENT after the
                # delay; arrivals are swallowed (reference DelayWindowProcessor).
                all_types = jnp.full((E + B,), EventType.CURRENT, jnp.int8)
                cur_out = jnp.zeros((B,), bool)
                n_cur, lead = jnp.int32(0), jnp.zeros((E,), jnp.int32)
            else:
                cur_out, n_cur, lead = cur_valid, n_valid32, trig
            total = n_exp + n_cur
            rank_exp = jnp.where(expires, pe + lead, total + pe - n_exp)
            rank_cur = jnp.where(cur_out, p + pops,
                                 total + (E - n_exp) + p - n_cur)
            order = jnp.zeros((E + B,), jnp.int32).at[rank_exp].set(pe) \
                .at[rank_cur].set(E + p)
        with stage("window/fetch"):
            chunk = _gather_chunk_packed(
                order, all_mat, all_emit, jnp.concatenate([expires, cur_out]),
                all_types, self.layout)

        # ---- ring update ----
        # in place, after every read of the old ring. XLA's TPU pipeline
        # orders the two itself; its CPU pipeline copies the whole ring
        # unless the write DEPENDS on the read, so it is made to, by a
        # no-op: n_valid32 <= B always, and `held` is 0 or 1
        with stage("window/append"):
            held = (cand_mat[0, 0] >> 31).astype(jnp.int32)
            new_ring = _append_packed(state.ring, comp_mat, state.appended,
                                      jnp.minimum(n_valid32, B + held))

        # live rows overwritten by ring wrap (a time window holding more
        # than C un-expired rows): new excess this step, monotone
        expired1 = state.expired + n_exp.astype(jnp.int64)
        over0 = jnp.maximum(state.appended - state.expired - self.C, 0)
        over1 = jnp.maximum(appended1 - expired1 - self.C, 0)
        new_state = SlidingState(
            ring=new_ring,
            appended=appended1,
            expired=expired1,
            wm=wm,
            overflow=state.overflow + jnp.maximum(over1 - over0, 0),
            deferred=deferred,
            live_hwm=jnp.maximum(state.live_hwm, appended1 - expired1),
        )
        return new_state, chunk

    def contents(self, state: SlidingState, now: jax.Array):
        ring_cols, ring_ts = _unpack_rows(state.ring, self.layout)
        live = _ring_live_mask(self.C, state.expired, state.appended)
        if self.time_ms is not None:
            # probe-time expiry: rows past their deadline are out even if no
            # batch has flushed them yet
            live = live & (ring_ts + jnp.int64(self.time_ms) > now)
        return ring_cols, ring_ts, live


# --------------------------------------------------------------------------- #
# batch (tumbling) windows: lengthBatch, timeBatch, batch
# --------------------------------------------------------------------------- #


class BatchState(NamedTuple):
    ring_cols: dict
    ring_ts: jax.Array
    appended: jax.Array  # int64 total arrivals
    flushed: jax.Array  # int64 arrivals already emitted (flush boundary)
    prev_start: jax.Array  # int64 start overall idx of the previous flush
    epoch_base: jax.Array  # int64 ts base for time flushes (first-event ts)
    has_base: jax.Array  # bool
    wm: jax.Array  # int64 external-time watermark (externalTimeBatch only)


class LengthBatchState(NamedTuple):
    ring: jax.Array  # u32[W, C] packed rows (all columns + ts words)
    appended: jax.Array  # int64 total valid arrivals ever
    flushed: jax.Array  # int64 arrivals already emitted (a multiple of N)


class LengthBatchWindow(WindowOp):
    """lengthBatch(N): tumbling count window. At each flush boundary emits
    [expired lanes of the previous flush, RESET, N current lanes]
    (reference: LengthBatchWindowProcessor.java:210-243).

    Runs on the packed ring (see the "packed-row payload" banner): the
    append and the fetch are contiguous slices, and because a count window's
    emission is a FIXED interleave — per completing flush `[N expired if a
    previous flush exists], RESET, N currents` — the source lane of every
    output lane is arithmetic on its lane number. One packed gather applies
    it; no emission keys, no merge, no per-column memory op."""

    def __init__(self, layout: dict, batch_cap: int, length: int,
                 expired_on: bool = True):
        if length <= 0:
            raise SiddhiAppCreationError("lengthBatch length must be > 0")
        self.layout = layout
        self.B = batch_cap
        self.N = length
        self.expired_on = expired_on
        self.C = 2 * length + batch_cap  # holds prev flush + partial + batch
        max_flushes = batch_cap // length + 2
        width = batch_cap + length  # current lanes possible
        if expired_on:
            width += batch_cap + length  # expired lanes
        width += max_flushes  # RESET lanes
        self.chunk_width = width
        self.W = _layout_words(layout)

    def init_state(self) -> LengthBatchState:
        return LengthBatchState(
            ring=jnp.zeros((self.W, self.C), jnp.uint32),
            appended=jnp.int64(0),
            flushed=jnp.int64(0),
        )

    def step(self, state: LengthBatchState, batch: EventBatch,
             now: jax.Array):
        B, N, L = self.B, self.N, self.chunk_width
        with stage("window/append"):
            comp_mat, n_valid32 = compact_packed(batch, self.layout)
        appended1 = state.appended + n_valid32.astype(jnp.int64)

        # Invariant: state.flushed is a multiple of N, so everything per lane
        # is int32 RELATIVE to it (vectorized s64 div/mod is emulated on TPU)
        r0 = (state.appended - state.flushed).astype(jnp.int32)  # partial len
        nf = (r0 + n_valid32) // N  # flushes completing in this batch

        # candidate rows, one contiguous fetch: overall indices
        # [flushed - lead, flushed + B + N) — the currents, led (where
        # expired lanes are emitted) by the previous flush. B + N + lead <= C.
        lead = N if self.expired_on else 0
        E = B + N + lead
        with stage("window/fetch"):
            rows = _fetch_rel_packed(state.ring, comp_mat, state.flushed - lead,
                                     state.appended, E)

        with stage("window/expire"):
            # output lane j -> (flush blk, position r within its block). A block
            # is [lead expired lanes, RESET, N currents]; the first flush ever
            # has no previous flush to expire, so its block starts at the RESET.
            blk_w = lead + 1 + N
            skip = jnp.where(state.flushed == 0, jnp.int32(lead), jnp.int32(0))
            j = jnp.arange(L, dtype=jnp.int32) + skip
            blk, r = j // blk_w, j % blk_w
            is_reset = r == lead
            is_cur = r > lead
            # row of `rows`: expired lane r of flush blk is event r of flush
            # blk - 1; current lane is event r - lead - 1 of flush blk; a RESET
            # reads the zero row appended at E
            src = jnp.where(is_reset, E,
                            jnp.clip(blk * N + r - is_cur.astype(jnp.int32),
                                     0, E - 1))
        with stage("window/fetch"):
            out = jnp.concatenate(
                [rows, jnp.zeros((self.W, 1), jnp.uint32)], axis=1)[:, src]
            cols, own_ts = _unpack_rows(out, self.layout)

        with stage("window/expire"):
            # RESET and expired lanes are stamped with the arrival completing
            # their flush (the reference re-stamps with current time): the last
            # current of each block — a strided slice of B//N + 1 stamps (no
            # more flushes can complete), each repeated over its block
            flush_ts = jnp.repeat(_packed_ts(rows[:, lead + N - 1::N]), blk_w)
            flush_ts = jax.lax.dynamic_slice(
                jnp.pad(flush_ts, (0, max(L + lead - flush_ts.shape[0], 0))),
                (skip,), (L,))

        chunk = EventBatch(
            ts=jnp.where(is_cur, own_ts, flush_ts),
            cols=cols,
            valid=blk < nf,
            types=jnp.where(
                is_cur, jnp.int8(EventType.CURRENT),
                jnp.where(is_reset, jnp.int8(EventType.RESET),
                          jnp.int8(EventType.EXPIRED))),
        )

        with stage("window/append"):
            new_state = LengthBatchState(
                ring=_append_packed(state.ring, comp_mat, state.appended,
                                    n_valid32),
                appended=appended1,
                flushed=state.flushed + (nf * N).astype(jnp.int64),
            )
        return new_state, chunk

    def contents(self, state: LengthBatchState, now: jax.Array):
        """Joins see the accumulating (unflushed) bucket (reference:
        BatchingFindableWindowProcessor over the current batch buffer)."""
        ring_cols, ring_ts = _unpack_rows(state.ring, self.layout)
        live = _ring_live_mask(self.C, state.flushed, state.appended)
        return ring_cols, ring_ts, live


def _emit_key(comp_pos, kind, within, B):
    """Emission sort key pair: hi = (completion batch position, kind),
    lo = within-flush sequence. Both int32 — sorted with a native two-key
    comparator instead of one emulated-s64 key (see `_sort_chunk`)."""
    hi = jnp.clip(comp_pos, -1, B).astype(jnp.int32) * 4 + kind
    return hi, within.astype(jnp.int32)


class TimeBatchWindow(WindowOp):
    """timeBatch(W): tumbling time window. Buckets are [base + k*W, base +
    (k+1)*W); a bucket flushes when an arrival or the watermark crosses its end
    (reference: TimeBatchWindowProcessor — scheduler-driven flush becomes
    watermark-driven). Emits [expired(prev bucket), RESET, currents] like
    lengthBatch."""

    def __init__(self, layout: dict, batch_cap: int, time_ms: int,
                 capacity: Optional[int] = None, expired_on: bool = True,
                 start_time: Optional[int] = None,
                 ts_attr: Optional[str] = None):
        self.layout = layout
        self.B = batch_cap
        self.W = time_ms
        self.expired_on = expired_on
        self.start_time = start_time
        #: externalTimeBatch(tsAttr, W): bucket clock from an event attribute
        #: (reference: ExternalTimeBatchWindowProcessor)
        self.ts_attr = ts_attr
        #: @app:eventTime allowed lateness (set by the query runtime) — see
        #: SlidingWindow.lateness_ms: buckets flush only once the trailing
        #: watermark crosses their end; 0 keeps the jaxpr unchanged
        self.lateness_ms = 0
        self.C = capacity or max(dtypes.config.default_window_capacity, 2 * batch_cap)
        self.E = max(batch_cap, 1024)  # max emitted current/expired lanes per step
        width = self.E + 1 + (self.E if expired_on else 0)
        self.chunk_width = width

    def init_state(self) -> BatchState:
        return BatchState(
            ring_cols=_empty_like_cols(self.layout, self.C),
            ring_ts=jnp.zeros((self.C,), dtypes.TS_DTYPE),
            appended=jnp.int64(0),
            flushed=jnp.int64(0),
            prev_start=jnp.int64(0),
            epoch_base=jnp.int64(self.start_time if self.start_time is not None else 0),
            has_base=jnp.bool_(self.start_time is not None),
            wm=jnp.int64(-(2**62)),
        )

    def step(self, state: BatchState, batch: EventBatch, now: jax.Array):
        B, E, C = self.B, self.E, self.C
        W = jnp.int64(self.W)
        comp_cols, comp_ts, n_valid, _ = compact(batch)
        if self.ts_attr is not None:
            comp_ts = comp_cols[self.ts_attr].astype(jnp.int64)
            mx = jnp.max(jnp.where(
                jnp.arange(B) < n_valid, comp_ts, jnp.int64(-(2**62))))
            if self.lateness_ms:
                # hold the bucket open until the watermark (max-seen minus
                # allowed lateness) passes its end — the ingress gate may
                # still release rows belonging to it
                mx = mx - jnp.int64(self.lateness_ms)
            wm = jnp.maximum(state.wm, mx)
            now = wm
        else:
            wm = state.wm
        appended1 = state.appended + n_valid

        # establish bucket base from the first-ever event
        first_ts = jnp.where(n_valid > 0, comp_ts[0], now)
        base = jnp.where(state.has_base, state.epoch_base, first_ts)
        has_base = state.has_base | (n_valid > 0)

        # Buckets are computed RELATIVE to now's bucket, in int32: one scalar
        # s64 division for now_bucket, then per-lane (ts - pivot) clamped into
        # int32 and divided by W as int32 (vectorized s64 division is
        # software-emulated on TPU and dominated this step's cost). Events
        # more than ~12 days (2^30 ms) from the watermark collapse onto the
        # extreme bucket — ordering/flush decisions stay monotone-correct.
        # DOCUMENTED DIVERGENCE: if one micro-batch spans >2^30 ms (e.g.
        # historical replay with a huge watermark jump), distinct NON-empty
        # far-past buckets merge into one flush group — one RESET and merged
        # per-bucket aggregates where the reference emits separate batches.
        # Events this far apart never share a micro-batch in live streams.
        now_bucket = (now - base) // W  # scalar
        pivot = base + now_bucket * W  # scalar; bucket(pivot) == now_bucket
        LIM = jnp.int64(1 << 30)
        W32 = jnp.int32(self.W) if self.W < (1 << 31) else None

        def bucket_rel(ts):  # → int32 bucket index relative to now's bucket
            d = jnp.clip(ts - pivot, -LIM, LIM)
            if W32 is None:  # window ≥ 2^31 ms: keep the emulated s64 path
                return (d // W).astype(jnp.int32)
            return d.astype(jnp.int32) // W32

        arr_bucket = bucket_rel(comp_ts)
        # final flushed bucket boundary: all buckets < flush_hi are emitted
        flush_hi = jnp.where(has_base, jnp.int32(0), jnp.int32(-(1 << 30)))

        # candidate currents: pending events [flushed, appended1) whose bucket
        # flushes this step. Per-lane offsets are int32 (see _gather_rel).
        pe = jnp.arange(E, dtype=jnp.int32)
        cur_exists_idx = pe < (appended1 - state.flushed).astype(jnp.int32)
        cur_cols, cur_ts = _gather_rel(
            state.ring_cols, state.ring_ts, comp_cols, comp_ts,
            state.appended, state.flushed, pe)
        cur_bucket = bucket_rel(cur_ts)
        cur_emit = cur_exists_idx & (cur_bucket < flush_hi)
        # trigger position: first arrival in a later bucket
        I32MAX = jnp.iinfo(jnp.int32).max
        padded_buckets = jnp.where(jnp.arange(B) < n_valid, arr_bucket, I32MAX)
        trig = searchsorted32(padded_buckets, cur_bucket + 1, side="left")
        cur_keys = _emit_key(trig, KIND_CURRENT, pe, B)

        # RESET: one per flushed bucket — approximate with one reset per step
        # boundary between buckets (sufficient: grouped_scan's reset zeroes all
        # keys; consecutive empty buckets collapse into one reset).
        # reset fires right after the last current of each flushed bucket; we
        # emit a reset lane per candidate position where the *next* candidate
        # is in a different bucket.
        next_bucket = jnp.concatenate([cur_bucket[1:], jnp.full((1,), -1, jnp.int32)])
        is_bucket_end = cur_emit & ((next_bucket != cur_bucket) | ~jnp.concatenate(
            [cur_emit[1:], jnp.zeros((1,), bool)]))
        reset_keys = _emit_key(trig, KIND_RESET, pe, B)
        reset_cols = _empty_like_cols(self.layout, E)
        reset_ts = cur_ts

        keys = [cur_keys, reset_keys]
        colss = [cur_cols, reset_cols]
        tss = [cur_ts, reset_ts]
        valids = [cur_emit, is_bucket_end]
        types = [jnp.full((E,), EventType.CURRENT, jnp.int8),
                 jnp.full((E,), EventType.RESET, jnp.int8)]

        if self.expired_on:
            # previous flushed bucket's events re-emitted as expired when the
            # next bucket flushes: events in [prev_start, flushed)
            exp_cols, exp_ts0 = _gather_rel(
                state.ring_cols, state.ring_ts, comp_cols, comp_ts,
                state.appended, state.prev_start, pe)
            exp_bucket = bucket_rel(exp_ts0)
            exp_emit = (pe < (state.flushed - state.prev_start).astype(jnp.int32)) & (
                exp_bucket + 1 < flush_hi)
            trig_e = searchsorted32(padded_buckets, exp_bucket + 2,
                                    side="left")
            exp_keys = _emit_key(trig_e, KIND_EXPIRED, pe, B)
            keys.append(exp_keys)
            colss.append(exp_cols)
            tss.append(exp_ts0)
            valids.append(exp_emit)
            types.append(jnp.full((E,), EventType.EXPIRED, jnp.int8))

        chunk = _merge_sorted_chunks(keys, colss, tss, valids, types,
                                     self.chunk_width)

        n_emitted = jnp.sum(cur_emit.astype(jnp.int64))
        new_flushed = state.flushed + n_emitted
        new_ring_cols, new_ring_ts = _scatter_append(
            state.ring_cols, state.ring_ts, comp_cols, comp_ts,
            state.appended, n_valid)
        new_state = BatchState(
            ring_cols=new_ring_cols,
            ring_ts=new_ring_ts,
            appended=appended1,
            flushed=new_flushed,
            prev_start=jnp.where(n_emitted > 0, state.flushed, state.prev_start),
            epoch_base=base,
            has_base=has_base,
            wm=wm,
        )
        return new_state, chunk

    def contents(self, state: BatchState, now: jax.Array):
        live = _ring_live_mask(self.C, state.flushed, state.appended)
        return state.ring_cols, state.ring_ts, live


# --------------------------------------------------------------------------- #
# pass-through (no window)
# --------------------------------------------------------------------------- #


class PassThroughWindow(WindowOp):
    """No window: batch lanes flow through as CURRENT (the query still gets
    chunk semantics so the selector path is uniform)."""

    shape_polymorphic = True  # step() is the identity — any lane count

    def __init__(self, layout: dict, batch_cap: int):
        self.layout = layout
        self.B = batch_cap
        self.chunk_width = batch_cap

    def init_state(self):
        return ()

    def step(self, state, batch: EventBatch, now: jax.Array):
        return state, batch

    def contents(self, state, now: jax.Array):
        """A windowless join side holds nothing (reference: a bare stream in a
        join keeps a zero-length window — only the arriving event matches)."""
        cols = {k: jnp.zeros((1,), dtype=dt) for k, dt in self.layout.items()}
        return cols, jnp.zeros((1,), dtypes.TS_DTYPE), jnp.zeros((1,), bool)


# --------------------------------------------------------------------------- #
# session window
# --------------------------------------------------------------------------- #


class SessionState(NamedTuple):
    ring_cols: dict
    ring_ts: jax.Array
    ring_session: jax.Array  # int64 session id per ring slot
    appended: jax.Array
    flushed: jax.Array
    last_ts: jax.Array  # ts of latest arrival (gap detection)
    session: jax.Array  # current session id
    has_events: jax.Array  # bool


class SessionWindow(WindowOp):
    """session(gap): events pass through as CURRENT immediately; when a gap
    larger than `gap` opens (next arrival or watermark), the closed session's
    events are re-emitted as EXPIRED (reference: SessionWindowProcessor.java —
    current chunk passes through:308, expired chunk of the previous session
    prepended on rollover:303-307). Keyed sessions (`session(gap, key)`)
    live in ops/windows_extra.py KeyedSessionWindow."""

    def __init__(self, layout: dict, batch_cap: int, gap_ms: int,
                 capacity: Optional[int] = None):
        self.layout = layout
        self.B = batch_cap
        self.gap = gap_ms
        self.C = capacity or max(dtypes.config.default_window_capacity,
                                 2 * batch_cap)
        self.E = max(batch_cap, 1024)
        self.chunk_width = self.B + self.E

    def init_state(self) -> SessionState:
        return SessionState(
            ring_cols=_empty_like_cols(self.layout, self.C),
            ring_ts=jnp.zeros((self.C,), dtypes.TS_DTYPE),
            ring_session=jnp.zeros((self.C,), jnp.int64),
            appended=jnp.int64(0),
            flushed=jnp.int64(0),
            last_ts=jnp.int64(0),
            session=jnp.int64(0),
            has_events=jnp.bool_(False),
        )

    def step(self, state: SessionState, batch: EventBatch, now: jax.Array):
        B, E, C = self.B, self.E, self.C
        gap = jnp.int64(self.gap)
        comp_cols, comp_ts, n_valid, _ = compact(batch)
        p = jnp.arange(B, dtype=jnp.int64)
        is_arr = p < n_valid

        # gap break before arrival i (vs previous arrival / state.last_ts)
        prev_ts = jnp.concatenate([state.last_ts[None], comp_ts[:-1]])
        brk = is_arr & state.has_events & (comp_ts - prev_ts > gap)
        # the very first arrival ever starts session 0 without a break
        brk = brk & ~((p == 0) & ~state.has_events)
        arr_session = state.session + jnp.cumsum(brk.astype(jnp.int64))
        session_after = jnp.where(n_valid > 0, arr_session[jnp.clip(n_valid - 1, 0, B - 1)],
                                  state.session)
        # watermark close: gap elapsed since the last event of the batch
        new_last = jnp.where(n_valid > 0, comp_ts[jnp.clip(n_valid - 1, 0, B - 1)],
                             state.last_ts)
        wm_close = state.has_events | (n_valid > 0)
        wm_close = wm_close & (now - new_last > gap)
        session_open = jnp.where(wm_close, session_after + 1, session_after)

        # ---- currents pass through ----
        keys_cur = p * 4 + KIND_CURRENT
        # ---- expired: ring events whose session < session_open ----
        o = state.flushed + jnp.arange(E, dtype=jnp.int64)
        in_ring = o < state.appended
        slot = jnp.clip(o, 0, None) % C
        ring_sess = state.ring_session[slot]
        exp_ring = in_ring & (ring_sess < session_open)
        # batch arrivals whose session closed within this same step
        exp_arr = is_arr & (arr_session < session_open)
        # trigger position: first arrival of a later session (or end of batch)
        arr_sess_padded = jnp.where(is_arr, arr_session, BIG)
        trig_ring = searchsorted32(arr_sess_padded, ring_sess + 1,
                                   side="left").astype(jnp.int64)
        trig_arr = searchsorted32(arr_sess_padded, arr_session + 1,
                                  side="left").astype(jnp.int64)
        keys_exp_ring = jnp.clip(trig_ring, 0, jnp.int64(B)) * 4 + KIND_EXPIRED
        keys_exp_arr = jnp.clip(trig_arr, 0, jnp.int64(B)) * 4 + KIND_EXPIRED

        all_keys = jnp.concatenate([keys_exp_ring, keys_exp_arr, keys_cur])
        all_cols = {k: jnp.concatenate([state.ring_cols[k][slot], comp_cols[k],
                                        comp_cols[k]])
                    for k in self.layout}
        all_ts = jnp.concatenate([state.ring_ts[slot], comp_ts, comp_ts])
        all_valid = jnp.concatenate([exp_ring, exp_arr, is_arr])
        all_types = jnp.concatenate([
            jnp.full((E,), EventType.EXPIRED, jnp.int8),
            jnp.full((B,), EventType.EXPIRED, jnp.int8),
            jnp.full((B,), EventType.CURRENT, jnp.int8),
        ])
        chunk = _sort_chunk(all_keys, all_cols, all_ts, all_valid, all_types,
                            self.chunk_width)

        # ---- ring update: append arrivals; account flushed ----
        new_cols, new_ts = _scatter_append(
            state.ring_cols, state.ring_ts, comp_cols, comp_ts,
            state.appended, n_valid)
        wslot = jnp.where(is_arr, (state.appended + p) % C, C)
        new_sess = state.ring_session.at[wslot].set(arr_session, mode="drop")
        n_flushed_ring = jnp.sum(exp_ring.astype(jnp.int64))
        n_flushed_arr = jnp.sum(exp_arr.astype(jnp.int64))
        new_state = SessionState(
            ring_cols=new_cols, ring_ts=new_ts, ring_session=new_sess,
            appended=state.appended + n_valid,
            flushed=state.flushed + n_flushed_ring + n_flushed_arr,
            last_ts=new_last,
            session=session_open,
            has_events=state.has_events | (n_valid > 0),
        )
        return new_state, chunk

    def contents(self, state: SessionState, now: jax.Array):
        live = _ring_live_mask(self.C, state.flushed, state.appended)
        return state.ring_cols, state.ring_ts, live


# --------------------------------------------------------------------------- #
# sort window
# --------------------------------------------------------------------------- #


class SortState(NamedTuple):
    cols: dict
    ts: jax.Array
    seq: jax.Array  # int64 arrival order (stable tiebreak)
    valid: jax.Array
    count: jax.Array  # int64 arrivals ever


class SortWindow(WindowOp):
    """sort(N, attr [,'asc'|'desc'], ...): keeps the N best events by sort
    key; each arrival emits [current, evicted-worst as EXPIRED] (reference:
    SortWindowProcessor.java:151-181). Batch form: merge buffer+batch, keep
    the N best; evicted set matches the reference's per-event processing
    (the kept set after any arrival order is the N best)."""

    def __init__(self, layout: dict, batch_cap: int, n: int,
                 sort_keys: list):  # [(attr, +1|-1)]
        self.layout = layout
        self.B = batch_cap
        self.N = n
        self.sort_keys = sort_keys
        self.chunk_width = batch_cap + batch_cap + n  # currents + evictable
        self.M = self.N + self.B  # merge width

    def init_state(self) -> SortState:
        N = self.N
        return SortState(
            cols=_empty_like_cols(self.layout, N),
            ts=jnp.zeros((N,), dtypes.TS_DTYPE),
            seq=jnp.zeros((N,), jnp.int64),
            valid=jnp.zeros((N,), bool),
            count=jnp.int64(0),
        )

    def _rank_key(self, cols: dict, valid: jax.Array):
        """Composite sort rank via successive stable argsorts (last key first);
        invalid lanes sort last."""
        M = valid.shape[0]
        perm = jnp.arange(M)
        for attr, order in reversed(self.sort_keys):
            k = cols[attr][perm].astype(jnp.float64)
            k = jnp.where(order < 0, -k, k)
            perm = perm[jnp.argsort(k, stable=True)]
        k = jnp.where(valid[perm], 0, 1)
        perm = perm[jnp.argsort(k, stable=True)]
        return perm  # positions in best-to-worst order

    def step(self, state: SortState, batch: EventBatch, now: jax.Array):
        B, N = self.B, self.N
        comp_cols, comp_ts, n_valid, _ = compact(batch)
        p = jnp.arange(B, dtype=jnp.int64)
        is_arr = p < n_valid

        m_cols = {k: jnp.concatenate([state.cols[k], comp_cols[k]])
                  for k in self.layout}
        m_ts = jnp.concatenate([state.ts, comp_ts])
        m_seq = jnp.concatenate([state.seq, state.count + p])
        m_valid = jnp.concatenate([state.valid, is_arr])

        from .groupby import invert_permutation
        perm = self._rank_key(m_cols, m_valid)
        keep_rank = invert_permutation(perm)
        kept = m_valid & (keep_rank < N)
        evicted = m_valid & ~kept

        # chunk: currents (arrival order) then evicted as EXPIRED
        keys_cur = p * 4 + KIND_CURRENT
        M = self.N + B
        keys_ev = jnp.full((M,), jnp.int64(B) * 4 + KIND_EXPIRED)
        all_keys = jnp.concatenate([keys_cur, keys_ev])
        all_cols = {k: jnp.concatenate([comp_cols[k], m_cols[k]])
                    for k in self.layout}
        all_ts = jnp.concatenate([comp_ts, jnp.full((M,), 0, dtypes.TS_DTYPE) + now])
        all_valid = jnp.concatenate([is_arr, evicted])
        all_types = jnp.concatenate([
            jnp.full((B,), EventType.CURRENT, jnp.int8),
            jnp.full((M,), EventType.EXPIRED, jnp.int8),
        ])
        chunk = _sort_chunk(all_keys, all_cols, all_ts, all_valid, all_types,
                            self.chunk_width)

        # new buffer: the N best lanes
        sel = perm[:N]
        new_state = SortState(
            cols={k: m_cols[k][sel] for k in self.layout},
            ts=m_ts[sel],
            seq=m_seq[sel],
            valid=m_valid[sel],
            count=state.count + n_valid,
        )
        return new_state, chunk

    def contents(self, state: SortState, now: jax.Array):
        return state.cols, state.ts, state.valid
