"""An exact device dictionary from 64-bit keys to dense slots, for state that
has a key axis (a partition's per-key windows: ops/keyed_window.py).

`ops/groupby.py`'s `KeyTable` serves `group by` on numbers: it keeps one
probe window of 16 scattered slots a lookup (2M gathered indices a
131,072-lane batch), hands ids out in hash order and folds the one key that
equals its pad into a neighbour. This table is built for the other job:

- **a lookup is one row gather.** Entries live in buckets of `BUCKET`, a
  bucket a row of `[NB, ROW]` int32 words (the keys' high words, their low
  words, `slot + 1`, and a quarter unused: a row is one 128-word tile, which
  is what the TPU keeps buckets major for), so the TPU fetches a lane's
  whole bucket by one index (it prices a gather by the index, not by the
  row: PERF.md, PR 31).
  Four entries a slot of capacity keep a bucket a quarter full on average;
  a key whose first bucket was full when it came lives in its second, which
  a lookup reads only when some lane missed in the first.
- **exact.** The stored words are the key itself, compared whole; an empty
  entry is marked by its slot word (0), not by a key value, so every 64-bit
  key is a key, the old pad sentinel included.
- **first come, first slotted.** New keys take slots in the order of their
  first lane, so which keys a full table turns away is the arrival order's
  to say, as upstream's per-key instances would be created. A key that finds
  no slot (capacity used up, or both its buckets full) resolves to the
  `capacity` sentinel: the caller drops and counts its lanes, it is never
  aliased onto another key.

Inserting is the rare path (a `lax.cond` on "some lane missed"): one sort of
the batch's keys to find each new key's first lane, and per bucket choice a
sort by bucket to give the newcomers of one bucket places side by side.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .search import stable_argsort_bounded

#: entries a bucket: with four entries a slot of capacity a bucket holds 8
#: on average when every slot is taken, and P(Poisson(8) >= 32) is 1e-10
BUCKET = 32
#: entries per slot of capacity
SPARE = 4
#: words a bucket's row
ROW = 128


class SlotTable(NamedTuple):
    rows: jax.Array  # int32[NB, ROW]: high words | low words | slot + 1 | -
    count: jax.Array  # int32: slots handed out


def buckets_for(capacity: int) -> int:
    return max(1, -(-SPARE * capacity // BUCKET))


def init_slot_table(capacity: int) -> SlotTable:
    return SlotTable(
        rows=jnp.zeros((buckets_for(capacity), ROW), jnp.int32),
        count=jnp.int32(0))


def _fmix(h):
    """murmur3's finalizer: every input bit reaches every output bit."""
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _bucket_choices(hi, lo, nb: int) -> list:
    h = _fmix(lo.astype(jnp.uint32)
              ^ _fmix(hi.astype(jnp.uint32) ^ jnp.uint32(0x9E3779B9)))
    first = (h % jnp.uint32(nb)).astype(jnp.int32)
    if nb == 1:
        return [first]
    g = _fmix(h ^ jnp.uint32(0x7F4A7C15))
    second = (first + 1 + (g % jnp.uint32(nb - 1)).astype(jnp.int32)) % nb
    return [first, second]


def _probe(rows, bucket, hi, lo):
    """Each lane's slot in its bucket (-1: not there)."""
    row = rows[bucket]  # [B, ROW], one index a lane
    ids = row[:, 2 * BUCKET:3 * BUCKET]
    match = ((row[:, :BUCKET] == hi[:, None])
             & (row[:, BUCKET:2 * BUCKET] == lo[:, None]) & (ids != 0))
    return jnp.max(jnp.where(match, ids, 0), axis=1) - 1


def run_first(start):
    """Per lane, the index of its run's first lane; `start[i]` says lane i
    opens a run of a sorted sequence."""
    lane = lax.iota(jnp.int32, start.shape[0])
    return lax.associative_scan(jnp.maximum, jnp.where(start, lane, 0))


def _rank_in_bucket(bucket, active, nb: int):
    """Per active lane, how many active lanes of its bucket come before it."""
    n = bucket.shape[0]
    key = jnp.where(active, bucket, nb)
    order = stable_argsort_bounded(key)
    s_key = key[order]
    start = jnp.concatenate([jnp.ones((1,), bool), s_key[1:] != s_key[:-1]])
    return jnp.zeros((n,), jnp.int32).at[order].set(
        lax.iota(jnp.int32, n) - run_first(start))


def lookup_or_insert(table: SlotTable, capacity: int, hi, lo, valid):
    """Resolve each valid lane's key `(hi, lo)` (two int32 words) to its
    slot, giving new keys the next slots in the order of their first lane.
    Returns (table, slot[B]); `slot == capacity` where the lane is invalid
    or its key found no slot."""
    n = hi.shape[0]
    nb = table.rows.shape[0]
    choices = _bucket_choices(hi, lo, nb)
    lane = lax.iota(jnp.int32, n)

    slot = jnp.where(valid, _probe(table.rows, choices[0], hi, lo), -1)
    need = valid & (slot < 0)
    for bucket in choices[1:]:
        found = lax.cond(
            jnp.any(need),
            lambda b=bucket: _probe(table.rows, b, hi, lo),
            lambda: jnp.full((n,), -1, jnp.int32))
        slot = jnp.where(need, found, slot)
        need = valid & (slot < 0)

    def insert(rows, count, need):
        # the batch's new keys, once each: lanes sorted by key, the lanes in
        # need first within a key's run (an invalid lane may carry any key)
        tag = jnp.where(need, lane, lane + n)
        s_hi, s_lo, s_tag = lax.sort((hi, lo, tag), num_keys=3,
                                     is_stable=False)
        run_start = jnp.concatenate([
            jnp.ones((1,), bool),
            (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])])
        is_new = run_start & (s_tag < n)
        # a new key's first lane stands for it; slots go by first lanes
        rep = jnp.zeros((n,), bool).at[
            jnp.where(is_new, s_tag, n)].set(True, mode="drop")
        new_slot = count + jnp.cumsum(rep.astype(jnp.int32)) - 1
        fits = rep & (new_slot < capacity)
        active = fits
        for bucket in choices:
            ids = rows[bucket][:, 2 * BUCKET:3 * BUCKET]  # after the choice before
            place = jnp.sum((ids != 0).astype(jnp.int32), axis=1) \
                + _rank_in_bucket(bucket, active, nb)
            ok = active & (place < BUCKET)
            at = jnp.where(ok, bucket, nb)
            rows = rows.at[at, place].set(hi, mode="drop")
            rows = rows.at[at, BUCKET + place].set(lo, mode="drop")
            rows = rows.at[at, 2 * BUCKET + place].set(new_slot + 1,
                                                       mode="drop")
            active = active & ~ok
        placed = jnp.where(fits & ~active, new_slot, capacity)
        # every lane of a new key's run takes its first lane's slot
        first = run_first(run_start)
        run_slot = jnp.where(
            is_new[first], placed[jnp.minimum(s_tag[first], n - 1)], capacity)
        given = jnp.zeros((n,), jnp.int32).at[
            jnp.where(s_tag < n, s_tag, n)].set(run_slot, mode="drop")
        return rows, count + jnp.sum(fits, dtype=jnp.int32), given

    rows, count, given = lax.cond(
        jnp.any(need), insert,
        lambda rows, count, need: (rows, count,
                                   jnp.full((n,), capacity, jnp.int32)),
        table.rows, table.count, need)
    slot = jnp.where(need, given, slot)
    return SlotTable(rows, count), jnp.where(valid, slot, capacity)
