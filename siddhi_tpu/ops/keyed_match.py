"""The pattern step's match by key: for each of P pending partial matches the
FIRST of B arriving lanes that carries the same key and arrived after the
entry's last captured event — one sort of the B + P keys and three prefix
scans, in place of the dense `[B, P]` condition mask (core/pattern_runtime.py).

    sort the B + P elements by (key, rank): a lane's rank is twice its
    arrival rank in the batch, an entry's is twice the arrival rank it must
    come AFTER, plus one, so an entry sits just before the first lane of its
    key that may take it;
    a reverse running minimum over the lanes' sorted positions gives every
    element the next lane at or after it;
    that lane is the entry's match if no key change lies between them.

Work and memory are (B + P) log (B + P); nothing here has B x P elements.
Keys are compared as raw device words, as `==` on the same attribute type
compiles to (`ops/expr_compile._compile_compare`): INT, BOOL and STRING
(dictionary codes) are one 32-bit word, LONG two.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .lanes import words

_NO_LANE = 2**62  # Python int literal: above every packed (position, lane)


def key_words(col: jax.Array) -> tuple:
    """A key column as the int32 words the sort compares: equal words iff
    equal values. Callers pass int32, bool or int64 columns."""
    return tuple(words(col))


def first_arrival_by_key(lane_words: tuple, lane_ok: jax.Array,
                         lane_rank: jax.Array, entry_words: tuple,
                         entry_ok: jax.Array, entry_after: jax.Array):
    """(found bool[P], lane int32[P]): per entry the lane of smallest rank
    among the `lane_ok` lanes whose key words equal the entry's and whose
    rank is above `entry_after`; `lane` is 0 where nothing was found.

    lane_words / entry_words: the key as equally many int32 arrays, [B] and
    [P]; lane_rank int32[B], distinct over the ok lanes; entry_after
    int32[P] (-1: any lane may take the entry)."""
    B = lane_ok.shape[0]
    P = entry_ok.shape[0]
    N = B + P
    words = [jnp.concatenate([lw, ew])
             for lw, ew in zip(lane_words, entry_words)]
    rank = jnp.concatenate([2 * lane_rank, 2 * entry_after + 1])
    live = jnp.concatenate([lane_ok, entry_ok])
    idx = jnp.arange(N, dtype=jnp.int32)
    # the payload names the element and, by its sign, whether it takes part
    sorted_ = lax.sort((*words, rank, jnp.where(live, idx, -1 - idx)),
                       num_keys=len(words) + 1)
    s_words, s_tag = sorted_[:len(words)], sorted_[-1]
    s_live = s_tag >= 0
    s_idx = jnp.where(s_live, s_tag, -1 - s_tag)
    is_lane = s_live & (s_idx < B)
    is_entry = s_live & (s_idx >= B)
    pos = jnp.arange(N, dtype=jnp.int32)
    # next lane at or after each position, with its batch lane in the low
    # word (one scan carries both)
    packed = jnp.where(is_lane,
                       (pos.astype(jnp.int64) << 32) | s_idx.astype(jnp.int64),
                       jnp.int64(_NO_LANE))
    nxt = lax.cummin(packed, reverse=True)
    cand_pos = (nxt >> 32).astype(jnp.int32)
    cand_lane = (nxt & jnp.int64(0xFFFFFFFF)).astype(jnp.int32)
    # first key change strictly after each position
    change = jnp.zeros((N,), bool)
    for w in s_words:
        change = change | (w != jnp.roll(w, 1))
    change_pos = lax.cummin(jnp.where(change.at[0].set(True), pos, N),
                            reverse=True)
    next_change = jnp.concatenate(
        [change_pos[1:], jnp.full((1,), N, jnp.int32)])
    hit = is_entry & (nxt < jnp.int64(_NO_LANE)) & (cand_pos < next_change)
    # every element writes somewhere of its own; all but the entries
    # write past the end and are dropped
    dest = jnp.where(is_entry, s_idx - B, P + pos)
    lane = jnp.full((P,), -1, jnp.int32).at[dest].set(
        jnp.where(hit, cand_lane, -1), mode="drop", unique_indices=True)
    return lane >= 0, jnp.maximum(lane, 0)
