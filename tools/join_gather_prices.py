#!/usr/bin/env python
"""What the memory operations of the join step cost on the device this runs
on: the layouts the multimap's per-slot words (arrival tag, key hash, next
position) can take under a chain walk and under the append's scatter, and
the ways a pair frame's columns can be gathered by pair lane
(`ops/join.py`, `core/join_runtime.py`; PERF.md, PR 37).

    python tools/join_gather_prices.py [--ring 131072] [--lanes 131072]

One JSON line a point (`name`, `ms`: the median over `--reps` executions
timed around `block_until_ready`, state donated; `empty` is the call's own
time, inside every other point). The first line names the device; a time
from the CPU backend is not a device number.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import siddhi_tpu  # noqa: E402,F401 — turns 64-bit types on
from siddhi_tpu.ops.lanes import gather_lanes  # noqa: E402
from siddhi_tpu.ops.windows import (_append_packed, _pack_rows,  # noqa: E402
                                    _unpack_rows)
from tools.keyed_ring_prices import _timed  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ring", type=int, default=131072)
    ap.add_argument("--lanes", type=int, default=131072)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--pair-factor", type=int, default=4)
    ap.add_argument("--reps", type=int, default=15)
    a = ap.parse_args()
    C, B, K, P = a.ring, a.lanes, a.steps, a.pair_factor * a.lanes
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "ring": C, "lanes": B, "steps": K, "pair_lanes": P}),
          flush=True)
    rng = np.random.default_rng(0)

    def u32(n):
        return jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64)
                           .astype(np.uint32))

    seq, hsh = u32(C), u32(C)
    nxt = jnp.asarray(rng.integers(0, C, C).astype(np.int32))
    start = jnp.asarray(rng.integers(0, C, B).astype(np.int32))
    nxt_u = lax.bitcast_convert_type(nxt, jnp.uint32)

    def point(name, fn, state, *args):
        jit = jax.jit(fn, donate_argnums=(0,))
        try:
            ms = _timed(jit, jax.tree_util.tree_map(jnp.copy, state), args,
                        a.reps)
            print(json.dumps({"name": name, "ms": round(ms, 3)}), flush=True)
        except Exception as e:  # noqa: BLE001 — one layout may not fit
            print(json.dumps({"name": name, "error": repr(e)[:200]}),
                  flush=True)

    point("empty", lambda acc, p: acc + p[0], jnp.zeros((), jnp.int32), start)

    # ---- the chain walk: K dependent steps, each reading a slot's three
    # words at the position the step before read
    def walk(read):
        def fn(acc, tab, p):
            for _ in range(K):
                s, h, n = read(tab, p)
                acc = acc + jnp.sum((s ^ h) & 1, dtype=jnp.uint32)
                p = lax.bitcast_convert_type(n, jnp.int32)
            return acc + jnp.sum(p).astype(jnp.uint32)
        return fn

    zero = jnp.zeros((), jnp.uint32)
    point("walk.three_arrays",
          walk(lambda t, p: (t[0][p], t[1][p], t[2][p])),
          zero, (seq, hsh, nxt_u), start)
    for W in (3, 4):
        rows = [seq, hsh, nxt_u] + [seq] * (W - 3)
        point(f"walk.words_major[{W},C]",
              walk(lambda t, p: tuple(t[:, p][:3])),
              zero, jnp.stack(rows, axis=0), start)
        point(f"walk.slots_major[C,{W}]",
              walk(lambda t, p: tuple(t[p].T[:3])),
              zero, jnp.stack(rows, axis=1), start)

    # ---- the append's write: B slots of three words at distinct positions
    dest = jnp.asarray(rng.permutation(C)[:B].astype(np.int32))
    vals = (u32(B), u32(B), u32(B))
    point("scatter.three_arrays",
          lambda t, d, v: tuple(x.at[d].set(y, mode="drop")
                                for x, y in zip(t, v)),
          (seq, hsh, nxt_u), dest, vals)
    for W in (3, 4):
        rows = [seq, hsh, nxt_u] + [seq] * (W - 3)
        vs = list(vals) + [vals[0]] * (W - 3)
        point(f"scatter.words_major[{W},C]",
              lambda t, d, v: t.at[:, d].set(jnp.stack(v, axis=0),
                                             mode="drop"),
              jnp.stack(rows, axis=0), dest, vs)
        point(f"scatter.slots_major[C,{W}]",
              lambda t, d, v: t.at[d].set(jnp.stack(v, axis=1), mode="drop"),
              jnp.stack(rows, axis=1), dest, vs)
    # the ring's way: the word scattered back to arrival order, then the
    # block written where the ring wrote its rows (a contiguous update)
    point("scatter.one_word_to_lane_order",
          lambda t, d, v: t.at[d].set(v, mode="drop"),
          jnp.zeros((B,), jnp.uint32),
          jnp.asarray(rng.permutation(B).astype(np.int32)), vals[0])

    back = jnp.asarray(rng.permutation(B).astype(np.int32))

    def ring_append(words_major, d, v):  # `_append_packed` wants [W, C]
        entry = jnp.stack([v[0], v[1], jnp.zeros_like(v[2]).at[d].set(
            v[2], mode="drop")])
        return _append_packed(words_major, entry,
                              jnp.int64(3 * C + C // 2 + 77), jnp.int32(B - 5))

    point("scatter.ring_append[3,C]", ring_append,
          jnp.stack([seq, hsh, nxt_u], axis=0), back, vals)
    point("scatter.ring_append[C,3]",
          lambda t, d, v: ring_append(t.T, d, v).T,
          jnp.stack([seq, hsh, nxt_u], axis=1), back, vals)

    # ---- the append's reads by the bucket order
    run = jnp.asarray(rng.permutation(B).astype(np.int32))
    cols4 = (nxt, seq, hsh, nxt)
    point("by_run.four_gathers",
          lambda acc, c, r: acc + sum(jnp.sum(x[r].astype(jnp.uint32))
                                      for x in c),
          zero, cols4, run)
    point("by_run.one_gather",
          lambda acc, c, r: acc + jnp.sum(c[1][r]), zero, cols4, run)
    point("by_run.packed[4,B]",
          lambda acc, c, r: acc + jnp.sum(jnp.stack(
              [lax.bitcast_convert_type(x, jnp.uint32) for x in c])[:, r]),
          zero, cols4, run)

    # ---- a pair frame: the batch's columns at P sorted pair lanes
    cols = {
        "symbol": jnp.asarray(rng.integers(0, 100000, B).astype(np.int32)),
        "price": jnp.asarray(rng.random(B).astype(np.float32)),
        "volume": jnp.asarray(rng.integers(0, 2**40, B)),
        "timestamp": jnp.asarray(rng.integers(0, 2**40, B)),
    }
    ts = jnp.asarray(rng.integers(0, 2**40, B))
    lane = jnp.asarray(np.sort(rng.integers(0, B, P)).astype(np.int32))
    layout = {k: v.dtype for k, v in cols.items()}

    def fold(c, t):
        acc = t.astype(jnp.float32)
        for v in c.values():
            acc = acc + v.astype(jnp.float32)
        return jnp.sum(acc)

    point("frame.a_gather_a_column",
          lambda acc, c, t, i: acc + fold({k: v[i] for k, v in c.items()},
                                          t[i]),
          jnp.zeros((), jnp.float32), cols, ts, lane)
    point("frame.gather_lanes[P,W]",
          lambda acc, c, t, i: acc + fold(*gather_lanes((c, t), i)),
          jnp.zeros((), jnp.float32), cols, ts, lane)
    point("frame.packed_rows[W,P]",
          lambda acc, c, t, i: acc + fold(*_unpack_rows(
              _pack_rows(c, t, layout)[:, i], layout)),
          jnp.zeros((), jnp.float32), cols, ts, lane)
    for name, col in (("price_f32", cols["price"]),
                      ("symbol_i32", cols["symbol"]), ("ts_i64", ts)):
        point(f"frame.one_column.{name}",
              lambda acc, c, i: acc + jnp.sum(c[i].astype(jnp.float32)),
              jnp.zeros((), jnp.float32), col, lane)


if __name__ == "__main__":
    main()
