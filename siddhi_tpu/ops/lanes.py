"""Columns as 32-bit words: how a step moves an 8-byte column through a
gather or a scatter. The TPU emulates 64-bit integers, and a scatter of
emulated int64 elements costs about twelve times a scatter of words (131,072
updates into 2^20 lanes: 15.8 ms against 1.3, PERF.md, PR 30), so an 8-byte
column crosses a scatter as two word columns and a gather packed side by side
with whatever shares its index. Arithmetic stays in the column's own dtype;
only the words' way to memory changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def words(a: jax.Array) -> list:
    """A column as 32-bit words (two for an 8-byte type, high first)."""
    if a.dtype.itemsize == 8:
        bits = a if a.dtype == jnp.int64 else lax.bitcast_convert_type(
            a, jnp.int64)
        return [(bits >> 32).astype(jnp.int32), bits.astype(jnp.int32)]
    if a.dtype.itemsize == 4:
        return [lax.bitcast_convert_type(a, jnp.int32)]
    return [a.astype(jnp.int32)]  # bool, int8, int16


def from_words(ws: list, dtype) -> jax.Array:
    if jnp.dtype(dtype).itemsize == 8:
        hi, lo = ws
        bits = (hi.astype(jnp.int64) << 32) \
            | lo.astype(jnp.uint32).astype(jnp.int64)
        return bits if dtype == jnp.int64 else lax.bitcast_convert_type(
            bits, dtype)
    if jnp.dtype(dtype).itemsize == 4:
        return lax.bitcast_convert_type(ws[0], dtype)
    return ws[0].astype(dtype)


def gather_lanes(tree, idx: jax.Array):
    """Every `[N]` leaf of `tree` at lanes `idx`, through ONE row gather of
    the leaves packed side by side as 32-bit words: a gather costs by the
    index, not by the row's width (a 2^20-lane gather of one word takes
    9.9 ms on a v5e, of an emulated int64 17.5: PERF.md, PR 30)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    split = [words(a) for a in leaves]
    flat = [w for ws in split for w in ws]
    # one word alone has nothing to pack with, and a [N, 1] row block is a
    # [N, 128] one to the TPU
    rows = flat[0][idx][:, None] if len(flat) == 1 \
        else jnp.stack(flat, axis=1)[idx]
    out, at = [], 0
    for a, ws in zip(leaves, split):
        out.append(from_words([rows[:, at + k] for k in range(len(ws))],
                              a.dtype))
        at += len(ws)
    return jax.tree_util.tree_unflatten(treedef, out)


def scatter_lanes(dst: jax.Array, slot: jax.Array, src) -> jax.Array:
    """`dst.at[slot].set(src, mode="drop")` for a `[P]` column; an 8-byte
    column goes as two columns of 32-bit words. The two word scatters equal
    the one 8-byte scatter only where NO TWO IN-BOUNDS ENTRIES OF `slot` ARE
    EQUAL (else one lane's high word could land beside another's low word):
    every caller states why its `slot` is so."""
    src = jnp.broadcast_to(jnp.asarray(src, dst.dtype), slot.shape)
    if dst.dtype.itemsize != 8:
        return dst.at[slot].set(src, mode="drop")
    return from_words([d.at[slot].set(w, mode="drop") for d, w in zip(
        words(dst), words(src))], dst.dtype)
