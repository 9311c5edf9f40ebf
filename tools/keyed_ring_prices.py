#!/usr/bin/env python
"""What the memory operations of a keyed window step cost on the device
this runs on: the layouts a `[K keys x L rows x W words]` ring can take
(`ops/keyed_window.py` keeps R, whole 128-word tiles a key, scattered with
sorted indices: PERF.md, PR 36), a bucketed key table's row gather, and the
sorts the step needs. XLA scatters along the major axis only: the layouts
with the keys minor (B, C) are transposed whole around their scatter, which
these timings include and a compile's `memory_analysis()` shows.

    python tools/keyed_ring_prices.py [--keys 1048576] [--lanes 131072]

One JSON line a point (`name`, `ms`: the median over `--reps` executions
timed around `block_until_ready`, state donated). The first line names the
device; a time from the CPU backend is not a device number.
"""

from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import siddhi_tpu  # noqa: E402,F401 — turns 64-bit types on


def _timed(fn, state, args, reps: int) -> float:
    state = fn(state, *args)
    jax.block_until_ready(state)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state = fn(state, *args)
        jax.block_until_ready(state)
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 20)
    ap.add_argument("--lanes", type=int, default=131072)
    ap.add_argument("--length", type=int, default=10)
    ap.add_argument("--words", type=int, default=8)
    ap.add_argument("--reps", type=int, default=15)
    a = ap.parse_args()
    K, B, L, W = a.keys, a.lanes, a.length, a.words
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "keys": K, "lanes": B, "length": L, "words": W}),
          flush=True)
    rng = np.random.default_rng(0)
    slot = jnp.asarray(rng.permutation(K)[:B].astype(np.int32))
    pos = jnp.asarray(rng.integers(0, L, B).astype(np.int32))
    new_row = jnp.asarray(rng.integers(0, 2**31, (B, W)).astype(np.uint32))

    def point(name, fn, state, *args):
        jit = jax.jit(fn, donate_argnums=(0,))
        try:
            ms = _timed(jit, state, args, a.reps)
            print(json.dumps({"name": name, "ms": round(ms, 3)}), flush=True)
        except Exception as e:  # noqa: BLE001 — one layout may not fit
            print(json.dumps({"name": name, "error": repr(e)[:200]}),
                  flush=True)

    # A: a device's rows side by side, [K, L*W] (the minor axis pads to 128)
    def a_step(ring, slot, pos, new_row):
        rows = ring[slot].reshape(B, L, W)
        hit = (jnp.arange(L)[None, :] == pos[:, None])[:, :, None]
        rows = jnp.where(hit, new_row[:, None, :], rows)
        return ring.at[slot].set(rows.reshape(B, L * W), mode="drop",
                                 unique_indices=True)

    point("A rows [K, L*W]: gather + scatter of whole rows", a_step,
          jnp.zeros((K, L * W), jnp.uint32), slot, pos, new_row)

    def a_gather(acc, ring, slot):
        return acc + jnp.sum(ring[slot], axis=1)

    ring_a = jnp.zeros((K, L * W), jnp.uint32)
    point("A rows [K, L*W]: gather alone", a_gather,
          jnp.zeros((B,), jnp.uint32), ring_a, slot)
    del ring_a

    # R: a key's state as one whole 128-word tile, [K, 128] (what
    # ops/keyed_window.py keeps): a row gathered a lane, a row scattered a
    # lane, and the same scatter told that its indices are sorted
    tile = -(-(L * W + 1) // 128) * 128
    rows = jnp.asarray(rng.integers(0, 2**31, (B, tile)).astype(np.uint32))
    ring_r = jnp.zeros((K, tile), jnp.uint32)
    point(f"R rows [K, {tile}]: gather alone", a_gather,
          jnp.zeros((B,), jnp.uint32), ring_r, slot)
    del ring_r

    def r_scatter(ring, slot, rows, **flags):
        return ring.at[slot].set(rows, mode="drop", unique_indices=True,
                                 **flags)

    point(f"R rows [K, {tile}]: scatter alone, indices in any order",
          r_scatter, jnp.zeros((K, tile), jnp.uint32), slot, rows)
    point(f"R rows [K, {tile}]: scatter alone, indices sorted and said so",
          functools.partial(r_scatter, indices_are_sorted=True),
          jnp.zeros((K, tile), jnp.uint32), jnp.sort(slot), rows)

    # B: words major, [L*W, K]
    def b_step(ring, slot, pos, new_row):
        cols = ring[:, slot].reshape(L, W, B)
        hit = (jnp.arange(L)[:, None] == pos[None, :])[:, None, :]
        cols = jnp.where(hit, new_row.T[None, :, :], cols)
        return ring.at[:, slot].set(cols.reshape(L * W, B), mode="drop",
                                    unique_indices=True)

    point("B columns [L*W, K]: gather + scatter of whole columns", b_step,
          jnp.zeros((L * W, K), jnp.uint32), slot, pos, new_row)

    # C: the packed ring as SlidingWindow has it, [W, K*L]
    def c_step(state, slot, pos, new_row):
        ring, _ = state
        idx = (slot[:, None] * L + jnp.arange(L, dtype=jnp.int32)[None, :])
        rows = ring[:, idx.reshape(-1)]
        ring = ring.at[:, slot * L + pos].set(new_row.T, mode="drop",
                                              unique_indices=True)
        return ring, jnp.sum(rows.reshape(W, B, L), axis=(0, 2))

    point("C packed [W, K*L]: gather of B*L lanes + scatter of B", c_step,
          (jnp.zeros((W, K * L), jnp.uint32), jnp.zeros((B,), jnp.uint32)),
          slot, pos, new_row)

    # C1: one word of it (the aggregate's argument alone)
    def c1_step(state, slot, pos, new_row):
        ring, _ = state
        idx = (slot[:, None] * L + jnp.arange(L, dtype=jnp.int32)[None, :])
        vals = ring[3, idx.reshape(-1)]
        ring = ring.at[:, slot * L + pos].set(new_row.T, mode="drop",
                                              unique_indices=True)
        return ring, jnp.sum(vals.reshape(B, L), axis=1)

    point("C1 packed [W, K*L]: gather of ONE word at B*L lanes + scatter",
          c1_step,
          (jnp.zeros((W, K * L), jnp.uint32), jnp.zeros((B,), jnp.uint32)),
          slot, pos, new_row)

    # the key table: one bucket row a lane, [NB, 3*S]
    for S in (8, 16):
        NB = max(4 * K // S, 1)
        bucket = jnp.asarray(rng.integers(0, NB, B).astype(np.int32))

        def t_step(acc, table, bucket):
            return acc + jnp.sum(table[bucket], axis=1)

        table = jnp.zeros((NB, 3 * S), jnp.int32)
        point(f"table [4K/{S}, {3 * S}]: one bucket-row gather a lane",
              t_step, jnp.zeros((B,), jnp.int32), table, bucket)

        def t_write(table, bucket, pos, new_row):
            t3 = table.reshape(NB, 3, S)
            t3 = t3.at[bucket, :, pos % S].set(
                new_row[:, :3].astype(jnp.int32), mode="drop")
            return t3.reshape(NB, 3 * S)

        point(f"table [4K/{S}, {3 * S}]: scatter of one entry a lane",
              t_write, table, bucket, pos, new_row)

    # the sorts
    hi = jnp.asarray(rng.integers(-2**31, 2**31, B).astype(np.int32))
    lo = jnp.asarray(rng.integers(0, 2**32, B).astype(np.uint32))
    lane = jnp.arange(B, dtype=jnp.int32)

    def s3(acc, hi, lo, lane):
        return acc + lax.sort((hi, lo, lane), num_keys=3, is_stable=False)[2]

    point("sort: three keys (hi, lo, lane), unstable", s3,
          jnp.zeros((B,), jnp.int32), hi, lo, lane)

    def s1(acc, slot):
        return acc + jnp.argsort(slot, stable=True).astype(jnp.int32)

    point("sort: stable argsort of int32 slots", s1,
          jnp.zeros((B,), jnp.int32), slot)

    def s1u(acc, slot, lane):
        return acc + lax.sort((slot, lane), num_keys=2, is_stable=False)[1]

    point("sort: (slot, lane) two keys, unstable", s1u,
          jnp.zeros((B,), jnp.int32), slot, lane)


if __name__ == "__main__":
    main()
