"""`#window.length(L)` with a key axis in its state: what a value partition
over one stream with one inner query needs, in one step a batch.

Upstream clones the inner query per key (core/partition/); the TPU plan
(SURVEY.md, "Partitions") is "a key axis in state arrays … no cloning —
state is just bigger arrays". Here that is one ring of `K` keys x `L` rows
of `W` packed words (`ops/windows.py` `_pack_rows`) and the keys' cursors:

    ring : u32[K, R]   a key's row: word `q * W + w` is word `w` of its `q`-th
                       ring row; word `L * W`: how many rows the key has
                       appended (kept below 2L; its residue mod L is the
                       write position); R: `L * W + 1` rounded up to 128

A key's state is one row, fetched and written back by ONE index a lane: the
TPU prices a gather and a scatter by the index, and a `[W, K * L]` ring in
`SlidingWindow`'s layout would take `B * L` of them (34 ms against 3 for
131,072 lanes: PERF.md, PR 36). Keys major, because XLA scatters along the
major axis only: given a `[L * W + 1, K]` ring it transposes the whole ring
into this layout and back around the scatter, every step, on the TPU as on
the CPU (a temporary the size of the ring, a cost that follows `K`). And
whole tiles of 128 words: the TPU lays a `[K, 81]` array out keys minor by
itself, to save the padding, and then transposes it around the scatter all
the same (compiled for a described v5e: PERF.md, PR 36).

One step, whose cost follows the batch and never `K`:

1. `window/route`: each lane's key to its slot (`ops/slot_table.py`; a key
   that finds none is counted in `dropped` and its lanes leave the batch),
   the lanes ordered by (slot, lane), so a key's lanes of this batch stand
   side by side in arrival order: lane `p` of rank `r` in its run sees the
   `r` lanes before it and its key's newest `L - 1 - r` ring rows.
2. `window/fetch`: every lane's key row, gathered once.
3. the caller aggregates over `KeyedWindow.window_reduce` (ring part by
   membership mask, batch part by `L` shifted copies of the sorted batch: no
   gather of `B * L` rows);
4. `window/append`: the last lane of each run writes its key's row back
   with the run's newest `min(m, L)` rows laid over it and the cursor moved:
   one scatter of whole rows to distinct, rising slots, in place on a
   donated ring. A key with more than `L` lanes in the batch leaves its newest `L`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..telemetry.tracing import stage
from .groupby import _OPS
from .lanes import words
from .search import stable_argsort_bounded, stable_partition_order
from .slot_table import (
    SlotTable,
    init_slot_table,
    lookup_or_insert,
    run_first,
)
from .windows import _layout_words, _pack_rows


class KeyedWindowState(NamedTuple):
    table: SlotTable
    ring: jax.Array  # u32[K, R]
    dropped: jax.Array  # int64 lifetime lanes whose key found no slot


class KeyedWindow(NamedTuple):
    """What one step's routing and fetch give the aggregates and the
    append, all in (slot, lane) order."""

    order: jax.Array  # int32[B]: lane at each sorted position
    slot: jax.Array  # int32[B]: the lane's slot (K: none)
    count: jax.Array  # int32[B]: rows the key appended before the batch
    lane_live: jax.Array  # bool[B], LANE order: valid, and its key has a slot
    rank: jax.Array  # int32[B]: lanes of the same key before it in the batch
    rows: jax.Array  # u32[W, B] the batch's packed rows
    held: jax.Array  # u32[L, W, B] the key's ring rows before the batch
    in_window: jax.Array  # bool[L, B]: ring row q is in this lane's window


def key_words(key: jax.Array) -> tuple:
    """A key column as two int32 words (high, low): injective, which is all
    the table asks."""
    ws = words(key)
    return (ws[0], ws[1]) if len(ws) == 2 \
        else (jnp.zeros(key.shape, jnp.int32), ws[0])


class KeyedLengthWindow:
    def __init__(self, layout: dict, length: int, capacity: int) -> None:
        self.layout = layout
        self.L = int(length)
        self.K = int(capacity)
        self.W = _layout_words(layout)
        #: words a key: its L rows and its count, in whole 128-word tiles
        self.R = -(-(self.L * self.W + 1) // 128) * 128

    def init_state(self) -> KeyedWindowState:
        return KeyedWindowState(
            table=init_slot_table(self.K),
            ring=jnp.zeros((self.K, self.R), jnp.uint32),
            dropped=jnp.int64(0))

    def fetch(self, state: KeyedWindowState, key, batch):
        """Route and fetch. Returns (state with the table and the drop
        count moved on, the `KeyedWindow`)."""
        L, W, K = self.L, self.W, self.K
        B = batch.capacity
        lane = lax.iota(jnp.int32, B)
        with stage("window/route"):
            hi, lo = key_words(key)
            table, slot = lookup_or_insert(state.table, K, hi, lo,
                                           batch.valid)
            dropped = state.dropped + jnp.sum(
                batch.valid & (slot >= K), dtype=jnp.int64)
            order = stable_argsort_bounded(slot)
            mat = _pack_rows(batch.cols, batch.ts, self.layout)  # [W, B]
            packed = jnp.concatenate(
                [mat, lax.bitcast_convert_type(slot, jnp.uint32)[None, :]],
                axis=0)[:, order]
            rows = packed[:W]
            s_slot = lax.bitcast_convert_type(packed[W], jnp.int32)
            start = jnp.concatenate(
                [jnp.ones((1,), bool), s_slot[1:] != s_slot[:-1]])
            rank = lane - run_first(start)
        with stage("window/fetch"):
            # one row a lane, then lanes minor again: [R, B]
            fetched = state.ring[jnp.minimum(s_slot, K - 1)].T
            held = fetched[:L * W].reshape(L, W, B)
            count = fetched[L * W].astype(jnp.int32)
            # ring row q was written `age` appends ago (1: the newest)
            q = lax.iota(jnp.int32, L)[:, None]
            age = (count[None, :] - 1 - q) % L + 1
            in_window = age <= jnp.minimum(jnp.minimum(count, L),
                                           L - 1 - rank)[None, :]
        return (KeyedWindowState(table, state.ring, dropped),
                KeyedWindow(order, s_slot, count, slot < K, rank, rows, held,
                            in_window))

    def window_reduce(self, w: KeyedWindow, ring_vals, batch_vals, op: str):
        """Per lane (sorted order), `op` over its window: `ring_vals[L, B]`
        are the values of the held rows, `batch_vals[B]` of the batch's."""
        L = self.L
        combine, identity = _OPS[op](batch_vals.dtype)
        ident = jnp.asarray(identity, batch_vals.dtype)
        ring_vals = jnp.where(w.in_window, ring_vals, ident)
        out = ring_vals[0]
        for q in range(1, L):
            out = combine(out, ring_vals[q])
        B = batch_vals.shape[0]
        padded = jnp.concatenate(
            [jnp.full((L - 1,), ident, batch_vals.dtype), batch_vals])
        for j in range(L):
            shifted = padded[L - 1 - j:L - 1 - j + B]
            out = combine(out, jnp.where(w.rank >= j, shifted, ident))
        return out

    def append(self, state: KeyedWindowState, w: KeyedWindow):
        """Append: the ring with every key's run of this batch laid over
        its row."""
        L, W, K = self.L, self.W, self.K
        B = w.rows.shape[1]
        count, s_slot = w.count, w.slot
        with stage("window/append"):
            last = jnp.concatenate(
                [s_slot[1:] != s_slot[:-1], jnp.ones((1,), bool)])
            m = w.rank + 1  # at a run's last lane: the run's length
            padded = jnp.concatenate(
                [jnp.zeros((W, L - 1), jnp.uint32), w.rows], axis=1)
            q = lax.iota(jnp.int32, L)[:, None]
            new = w.held
            for j in range(L):
                # the run's j-th newest lane stands j places before its last
                # and goes to ring row (count + m - 1 - j) % L
                lands = (j < m)[None, :] & ((count + m - 1 - j)[None, :] % L
                                            == q)
                new = jnp.where(lands[:, None, :],
                                padded[None, :, L - 1 - j:L - 1 - j + B], new)
            total = count + m
            total = jnp.where(total >= 2 * L, L + total % L, total)
            rows = jnp.concatenate(
                [new.reshape(L * W, B), total.astype(jnp.uint32)[None, :],
                 jnp.zeros((self.R - L * W - 1, B), jnp.uint32)], axis=0).T
            # one lane a key writes, its run's last. The writers go first,
            # which leaves them in slot order: XLA's TPU scatter of rows
            # takes 2.6 ms where it is told the indices are sorted and 10.4
            # where it is not (131,072 rows into 2^20: PERF.md, PR 36), and
            # the row gather that brings the writers together 1.3. The rest
            # get indices beyond the ring, rising too, and are dropped.
            writes = (s_slot < K) & last
            perm = stable_partition_order(writes)
            lane = lax.iota(jnp.int32, B)
            at = jnp.where(lane < jnp.sum(writes, dtype=jnp.int32),
                           s_slot[perm], K + lane)
            ring = state.ring.at[at].set(
                rows[perm], mode="drop", unique_indices=True,
                indices_are_sorted=True)
        return state._replace(ring=ring)
