"""Device join kernels (reference: core/query/input/stream/join/JoinProcessor.java:45).

The reference walks each arriving event through `find()` on the opposite
window/table with a CompiledCondition (per-event linked-list probe, optionally
index-accelerated by the table's CollectionExecutors). The TPU redesign probes
a whole micro-batch at once with two strategies chosen at plan time:

- **equi join** (the common case; BASELINE config 5): equality conjuncts
  `A.x == B.y` are extracted from the ON condition; build-side rows are
  key-hash sorted per probe and candidates located by `searchsorted`, bounded
  to K candidates per probe lane. Hashes only generate candidates — the exact
  ON condition re-verifies every pair, so hash collisions cannot produce false
  matches. This is a sort-merge join: one sort of the build ring + one
  binary-search per probe lane, all inside the query's fused XLA program.
  A sliding-window build side skips the sort: `MultimapState` indexes its
  ring incrementally and `probe_equi_mm` walks bucket chains.
- **cross join** fallback for ON conditions with no equality conjunct: a
  [B, C] mask with per-row top-K selection. Requires a small build side.

Both produce a fixed-width pair block: [B*K] matched lanes (+[B] outer lanes
for left/right/full outer), each pair carrying both frames' columns.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .search import (
    searchsorted32,
    stable_argsort_bounded,
    stable_partition_order,
)

from ..core import dtypes
from ..errors import SiddhiAppCreationError
from ..query_api.definition import AttributeType
from ..query_api.expression import (And, Compare, CompareOp, Constant,
                                    Expression, Variable)
from .expr_compile import CompiledExpr, Scope, TypeResolver, compile_expression
from .groupby import hash_columns32
from .windows import _append_packed

BIGKEY = np.uint32(0xFFFFFFFF)  # numpy literal — see ops/windows.py BIG note


def split_conjuncts(expr: Optional[Expression]) -> list[Expression]:
    if expr is None:
        return []
    if isinstance(expr, And):
        return split_conjuncts(expr.left) + split_conjuncts(expr.right)
    return [expr]


def collect_vars(expr):
    """All Variable leaves of a condition AST — ONE walker shared by the
    join planner and the condition-based store fallback."""
    out = []

    def walk(e):
        if isinstance(e, Variable):
            out.append(e)
            return
        for a in ("left", "right", "expression"):
            sub = getattr(e, a, None)
            if isinstance(sub, Expression):
                walk(sub)
        for p in getattr(e, "parameters", ()) or ():
            if isinstance(p, Expression):
                walk(p)

    if expr is not None:
        walk(expr)
    return out


def frames_of(expr: Expression, resolver: TypeResolver) -> set:
    """Frame refs referenced by an expression (resolving unqualified vars)."""
    out: set = set()

    def walk(e):
        if isinstance(e, Variable):
            ref, _, _ = resolver.resolve(e)
            out.add(ref if ref is not None else resolver.default_frame)
            return
        for attr in ("left", "right", "expression"):
            sub = getattr(e, attr, None)
            if isinstance(sub, Expression):
                walk(sub)
        for p in getattr(e, "parameters", ()) or ():
            if isinstance(p, Expression):
                walk(p)

    walk(expr)
    return out


@dataclass
class JoinPlan:
    """Extracted equi-keys + residual condition for one (probe, build) pair."""

    probe_keys: list  # CompiledExpr evaluated on the probe frame
    build_keys: list  # CompiledExpr evaluated on the build frame
    residual: Optional[CompiledExpr]  # full ON condition (pair-verified)


def plan_join(on: Optional[Expression], probe_frame: str, build_frame: str,
              resolver: TypeResolver, registry) -> JoinPlan:
    probe_keys: list = []
    build_keys: list = []
    for conj in split_conjuncts(on):
        if isinstance(conj, Compare) and conj.op == CompareOp.EQUAL \
                and not _string_constant(conj):
            lf = frames_of(conj.left, resolver)
            rf = frames_of(conj.right, resolver)
            if lf <= {probe_frame} and rf <= {build_frame}:
                probe_keys.append(compile_expression(conj.left, resolver, registry))
                build_keys.append(compile_expression(conj.right, resolver, registry))
                continue
            if lf <= {build_frame} and rf <= {probe_frame}:
                probe_keys.append(compile_expression(conj.right, resolver, registry))
                build_keys.append(compile_expression(conj.left, resolver, registry))
                continue
    residual = compile_expression(on, resolver, registry) if on is not None else None
    if residual is not None and residual.type != AttributeType.BOOL:
        raise SiddhiAppCreationError("join ON condition must be boolean")
    return JoinPlan(probe_keys, build_keys, residual)


def _string_constant(conj: Compare) -> bool:
    """`attr == 'text'`: a filter of one side, never a hash key (the
    constant compiles to no device value)."""
    return any(isinstance(e, Constant) and e.type_name == "string"
               for e in (conj.left, conj.right))


def _hash_exprs(keys: Sequence[CompiledExpr], scope: Scope) -> jax.Array:
    # avoid colliding with the BIGKEY invalid sentinel
    h = hash_columns32([k(scope) for k in keys])
    return jnp.where(h == BIGKEY, jnp.uint32(0xFFFFFFFE), h)


def probe_equi(plan: JoinPlan, probe_scope: Scope, probe_valid: jax.Array,
               build_cols: dict, build_ts: jax.Array, build_valid: jax.Array,
               build_frame: str, k_max: int):
    """Candidate pairs via sort-merge on key hashes.

    Returns (probe_lane[P], build_row[P], pair_valid[P]) with P = B*k_max.
    """
    B = probe_valid.shape[0]
    C = build_ts.shape[0]

    bscope = Scope()
    bscope.add_frame(build_frame, build_cols, build_ts, build_valid, default=True)
    bkeys = jnp.where(build_valid, _hash_exprs(plan.build_keys, bscope), BIGKEY)
    pkeys = _hash_exprs(plan.probe_keys, probe_scope)

    order = jnp.argsort(bkeys, stable=True)  # invalid rows sort last
    sorted_keys = bkeys[order]
    start = searchsorted32(sorted_keys, pkeys, side="left")

    k = jnp.arange(k_max)
    pos = start[:, None] + k[None, :]  # [B,K]
    pos_c = jnp.clip(pos, 0, C - 1)
    cand_valid = (pos < C) & (sorted_keys[pos_c] == pkeys[:, None]) & \
        probe_valid[:, None]
    build_row = order[pos_c]  # [B,K]

    probe_lane = jnp.broadcast_to(jnp.arange(B)[:, None], (B, k_max)).reshape(-1)
    return probe_lane, build_row.reshape(-1), cand_valid.reshape(-1)


def compact_pairs(build_row: jax.Array, pair_valid: jax.Array, k_max: int,
                  pair_cap: int):
    """Compact the sparse [B*k_max] candidate block to `pair_cap` lanes.

    Matches are typically ~1 per probe event, so downstream frame gathers,
    residual verification, and the selector would otherwise run at k_max x
    the real pair count. The block is [B, k_max] in probe-major order (every
    `probe_*` above hands it over so), and no candidate lane is scattered:

    1. each probe's row is left-justified by a fused k_max x k_max one-hot
       reduce (dense; order within a probe kept, oldest first);
    2. an exclusive cumsum of the per-probe counts gives each probe's first
       pair lane, and ONE scatter of at most B single words writes the
       probe's number there (sorted; probes with no match share the lane of
       the next probe that has one, which `max` lets win);
    3. a cummax fills the probe number forward over its run of pair lanes —
       this IS the pair's probe lane — and a second one fills the run's
       start, so a pair's place within its probe costs no gather;
    4. one `pair_cap`-lane gather reads the survivors from the
       left-justified rows.

    Candidate order (probe-lane major) is preserved, keeping emission order
    intact. Pairs beyond pair_cap are dropped, a probe that straddles the
    cap keeping its oldest (bounded fan-out, like k_max — size via
    dtypes.config.join_pair_cap_factor); lanes past the survivors read 0.
    Returns (probe_lane i32[pair_cap], build_row i32[pair_cap],
    pair_valid bool[pair_cap])."""
    K = k_max
    ok = pair_valid.reshape(-1, K)
    cand = build_row.astype(jnp.int32).reshape(-1, K)
    B = ok.shape[0]
    rank = jnp.cumsum(ok.astype(jnp.int32), axis=1) - 1
    count = rank[:, -1] + 1
    hit = ok[:, :, None] & (
        rank[:, :, None] == jnp.arange(K, dtype=jnp.int32)[None, None, :])
    packed = jnp.sum(jnp.where(hit, cand[:, :, None], 0), axis=1,
                     dtype=jnp.int32)  # [B, K]

    ends = jnp.cumsum(count)
    off = ends - count
    j = jnp.arange(pair_cap, dtype=jnp.int32)
    heads = jnp.full((pair_cap,), -1, jnp.int32).at[off].max(
        jnp.arange(B, dtype=jnp.int32), indices_are_sorted=True, mode="drop")
    lane = jax.lax.cummax(heads)
    start = jax.lax.cummax(jnp.where(heads >= 0, j, 0))
    pv = j < jnp.minimum(ends[-1], pair_cap)
    lane = jnp.where(pv, lane, 0)
    src = jnp.where(pv, lane * K + (j - start), 0)
    rows = packed.reshape(-1).at[src].get(mode="promise_in_bounds")
    return lane, jnp.where(pv, rows, 0), pv


class _MultimapFields(NamedTuple):
    heads: jax.Array  # i32[H] ring position of the newest entry per bucket
    slots: jax.Array  # u32[C, 3] a ring slot's (arrival tag, key hash, next)


class MultimapState(_MultimapFields):
    """Incrementally maintained hash multimap over a FIFO window ring.

    Replaces the per-step build-side sort of `probe_equi` for sliding-window
    build sides (the reference's per-event `find()` against the opposite
    window, JoinProcessor.java:140-143): entries are inserted as rows append
    to the ring and never explicitly deleted — FIFO overwrite invalidates
    them, and chains through an overwritten slot terminate safely because
    every entry past it is older and therefore also overwritten.

    All 32-bit words (int64 lane math is emulated on TPU). Entries are
    addressed by RING POSITION, and a slot's words — its row's arrival index
    mod 2^32, its full key hash, the position of the next-older chain entry
    (an i32 bit-cast) — are ONE row of `slots`: a gather costs by the index,
    not by the row, and a chain step is one row gather (PERF.md, PR 37: a
    third of a word gather's time; the TPU keeps the table slots-minor by
    itself). Liveness rides the arrival tag compared by wraparound age
    (`appended - tag`), exact while the window stays under 2^32 rows (every
    slot is rewritten each C arrivals: an alias needs a 2^32-event gap).
    """

    __slots__ = ()

    def __new__(cls, heads, slots, *parent):
        if parent:  # a snapshot from before PR 37: heads, nexts, hash, seq
            slots = np.stack([np.asarray(w).view(np.uint32)
                              for w in (parent[1], parent[0], slots)], axis=1)
        return super().__new__(cls, heads, slots)


def multimap_init(ring_capacity: int, n_buckets: int) -> MultimapState:
    empty = np.array([0xFFFFFFFF, 0, 0xFFFFFFFF], np.uint32)  # next = -1
    return MultimapState(jnp.full((n_buckets,), -1, jnp.int32),
                         jnp.broadcast_to(empty, (ring_capacity, 3)))


def multimap_buckets(ring_capacity: int) -> int:
    """Power-of-two bucket count >= 16x the ring: cheap masking, and chains
    that are the probe's own matches almost alone. A walk examines
    `join_max_matches` chain entries whether they match or not, so a bucket
    shared with other keys spends the probe's budget on them: at 2x the ring
    (the first cut) a `length(100000)` window over 100k uniform keys had a
    chain of 17 or more in one window in 1,600 even under a uniform hash,
    and lost pairs in 5 of 17 served runs on the chip (PERF.md, PR 26); at
    16x it is one window in 670,000. Costs 64 B a ring slot."""
    h = 1
    while h < 16 * ring_capacity:
        h *= 2
    return h


def multimap_append(mm: MultimapState, hashes: jax.Array, live: jax.Array,
                    appended0: jax.Array) -> MultimapState:
    """Insert this batch's live rows, which the window appends (compacted,
    arrival order) at overall indices [appended0, appended0 + n_live).

    Vectorized intra-batch chaining: one [B] sort by bucket; within a bucket
    run rows link oldest <- newest, the run's oldest links to the bucket's
    previous head, and each run's END (the newest row) becomes the head —
    no atomics. A row's tag and position follow from its compacted lane, and
    the slots written are the ring's own: the links go back to arrival order
    by one word scatter, the entries in by the ring's contiguous append.
    """
    C = mm.slots.shape[0]
    H = mm.heads.shape[0]
    # mirror compact_packed: live rows first, stable → arrival order
    hashes = hashes[stable_partition_order(live)]
    n_live = jnp.sum(live, dtype=jnp.int32)
    j = jnp.arange(hashes.shape[0], dtype=jnp.int32)
    sortkey = jnp.where(j < n_live, (hashes & jnp.uint32(H - 1)).astype(
        jnp.int32), jnp.int32(H))
    run = stable_argsort_bounded(sortkey)  # bounded non-negative (<= H)
    b_s = sortkey[run]
    pos_s = (appended0 % C).astype(jnp.int32) + run
    pos_s = jnp.where(pos_s >= C, pos_s - C, pos_s)  # base + lane < 2C always
    same_as_prev = jnp.concatenate(
        [jnp.zeros((1,), bool), b_s[1:] == b_s[:-1]])
    old_head = mm.heads[jnp.clip(b_s, 0, H - 1)]
    prev_pos = jnp.concatenate([jnp.full((1,), -1, jnp.int32), pos_s[:-1]])
    next_val = jnp.where(same_as_prev, prev_pos, old_head)

    older = jnp.zeros_like(next_val).at[run].set(next_val)  # a permutation
    entry = jnp.stack([appended0.astype(jnp.uint32) + j.astype(jnp.uint32),
                       hashes, jax.lax.bitcast_convert_type(older, jnp.uint32)])
    slots = _append_packed(mm.slots.T, entry, appended0, n_live).T
    is_end = jnp.concatenate(
        [b_s[1:] != b_s[:-1], jnp.ones((1,), bool)]) & (b_s < H)
    hdest = jnp.where(is_end, b_s, jnp.int32(H))
    heads = mm.heads.at[hdest].set(pos_s, mode="drop")
    return MultimapState(heads, slots)


def multimap_probe(mm: MultimapState, probe_hash: jax.Array,
                   probe_valid: jax.Array, appended: jax.Array,
                   window_len: jax.Array, k_max: int):
    """Walk bucket chains for each probe lane; k_max candidates max, one
    row gather of the slot's entry (tag, hash, next) a step.

    Liveness is the u32 age test `0 < appended - tag <= window_len`,
    and the walk additionally requires ages to STRICTLY INCREASE: a chain
    diverted through an overwritten slot jumps to a newer row, the age
    drops, and the walk stops — no stale or duplicate candidates.

    Returns (cand_pos i32[B,K] ring positions oldest-first, cand_ok
    bool[B,K], truncated i32 — probe lanes whose chain still had live
    entries after k_max steps, i.e. potential matches never examined).
    """
    H = mm.heads.shape[0]
    app32 = appended.astype(jnp.uint32)
    wlen = window_len.astype(jnp.uint32)
    bucket = (probe_hash & jnp.uint32(H - 1)).astype(jnp.int32)
    pos = jnp.where(probe_valid, mm.heads[bucket], jnp.int32(-1))
    alive = probe_valid
    prev_age = jnp.zeros_like(app32, shape=pos.shape)
    cands, oks = [], []
    for k in range(k_max + 1):  # the last step only counts a LIVE tail
        ok_pos = alive & (pos >= 0)
        p = jnp.where(ok_pos, pos, 0)
        seq, hsh, nxt = mm.slots[p].T
        age = app32 - seq
        live = ok_pos & (age > prev_age) & (age <= wlen)
        if k == k_max:  # a dead or diverted tail is not a lost match
            truncated = jnp.sum(live, dtype=jnp.int32)
            break
        match = live & (hsh == probe_hash)
        cands.append(jnp.where(match, p, jnp.int32(0)))
        oks.append(match)
        alive = live
        prev_age = age
        pos = jax.lax.bitcast_convert_type(nxt, jnp.int32)
    # chains run newest → oldest; reverse so pair emission (and k_max
    # truncation) is oldest-first like the sorted probe path
    cand_pos = jnp.stack(cands[::-1], axis=1)
    cand_ok = jnp.stack(oks[::-1], axis=1)
    return cand_pos, cand_ok, truncated


def probe_equi_mm(plan: JoinPlan, probe_scope: Scope, probe_valid: jax.Array,
                  mm: MultimapState, appended: jax.Array,
                  window_len: jax.Array, k_max: int):
    """`probe_equi` against an incrementally maintained multimap: no build
    sort, no full-ring hash — only chain walks. Returns
    (probe_lane[P], build_row[P] i32 ring positions, pair_valid[P],
    truncated) with P = B*k_max."""
    B = probe_valid.shape[0]
    pkeys = _hash_exprs(plan.probe_keys, probe_scope)
    cand_pos, cand_ok, truncated = multimap_probe(
        mm, pkeys, probe_valid, appended, window_len, k_max)
    probe_lane = jnp.broadcast_to(
        jnp.arange(B)[:, None], (B, k_max)).reshape(-1)
    return probe_lane, cand_pos.reshape(-1), cand_ok.reshape(-1), truncated


def probe_cross(probe_valid: jax.Array, build_valid: jax.Array, k_max: int):
    """All (probe, build) candidates, bounded to the first k_max valid build
    rows per probe lane (small build sides only)."""
    B = probe_valid.shape[0]
    C = build_valid.shape[0]
    # rank of each build row among valid rows
    rank = jnp.cumsum(build_valid.astype(jnp.int32)) - 1
    # k-th valid build row index
    order = stable_partition_order(build_valid)  # valid rows first
    kth = order[jnp.clip(jnp.arange(k_max), 0, C - 1)]
    n_valid = jnp.sum(build_valid.astype(jnp.int32))
    kv = jnp.arange(k_max) < n_valid
    probe_lane = jnp.broadcast_to(jnp.arange(B)[:, None], (B, k_max)).reshape(-1)
    build_row = jnp.broadcast_to(kth[None, :], (B, k_max)).reshape(-1)
    pair_valid = (probe_valid[:, None] & kv[None, :]).reshape(-1)
    return probe_lane, build_row, pair_valid
