#!/usr/bin/env python
"""What the table write costs on the device this runs on: the whole
table-write program of a @PrimaryKey table on the device index
(`core/table_index.py` `make_write`, `update or insert`) at a deployment's
size, and the ways its gathers can take a batch's rows — a gather a column
(8-byte columns as emulated int64), two index gathers and a packed row
gather per run end, or one packed row gather into slot order and one per
run end (PERF.md §5, §6).

    python tools/table_write_prices.py [--rows 1048576] [--lanes 131072]

One JSON line a point (`name`, `ms`: the median over `--reps` executions
timed around `block_until_ready`, state donated; `empty` is the call's own
time, inside every other point). The first line names the device; a time
from the CPU backend is not a device number. Run from another checkout's
root (`python <this file>`) it times that checkout's program.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, os.getcwd())
sys.path.insert(1, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import siddhi_tpu  # noqa: E402,F401 — turns 64-bit types on
from siddhi_tpu.core import table_index  # noqa: E402
from siddhi_tpu.ops.lanes import from_words, gather_lanes, words  # noqa: E402
from siddhi_tpu.ops.slot_table import run_first  # noqa: E402
from tools.keyed_ring_prices import _timed  # noqa: E402

#: the registry's table (benchmarks/configs/registry_1m.json)
DTYPES = {"deviceID": jnp.int64, "roomNo": jnp.int32, "type": jnp.int32,
          "updated": jnp.int64}
SETS = ("roomNo", "type", "updated")


def words_major(tree, idx):
    """`gather_lanes` with the words stacked the other way: `[W, N]`, the
    lanes the minor axis (a `[N, W]` block pads W to 128 on the TPU)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    split = [words(a) for a in leaves]
    mat = jnp.stack([w for ws in split for w in ws])[:, idx]
    out, at = [], 0
    for a, ws in zip(leaves, split):
        out.append(from_words(list(mat[at:at + len(ws)]), a.dtype))
        at += len(ws)
    return jax.tree_util.tree_unflatten(treedef, out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--keys", type=int, default=1_000_000)
    ap.add_argument("--lanes", type=int, default=131072)
    ap.add_argument("--reps", type=int, default=15)
    a = ap.parse_args()
    C, K, B = a.rows, min(a.keys, a.rows), a.lanes
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "rows": C, "keys": K, "lanes": B,
                      "program_from": table_index.__file__}), flush=True)
    rng = np.random.default_rng(0)
    ids = rng.choice(np.arange(-2**62, 2**62, 2**40 + 7), K,
                     replace=False).astype(np.int64)

    def batch(devices):
        n = devices.size
        return ({"deviceID": jnp.asarray(ids[devices]),
                 "roomNo": jnp.asarray(rng.integers(0, 10000, n)
                                       .astype(np.int32)),
                 "type": jnp.asarray(rng.integers(0, 4, n).astype(np.int32)),
                 "updated": jnp.asarray(rng.integers(0, 2**40, n))},
                jnp.asarray(rng.integers(0, 2**40, n)), jnp.ones((n,), bool))

    def point(name, fn, state, *args):
        jit = jax.jit(fn, donate_argnums=(0,))
        try:
            ms = _timed(jit, jax.tree_util.tree_map(jnp.copy, state), args,
                        a.reps)
            print(json.dumps({"name": name, "ms": round(ms, 3)}), flush=True)
        except Exception as e:  # noqa: BLE001 — one form may not fit
            print(json.dumps({"name": name, "error": repr(e)[:200]}),
                  flush=True)

    zero = jnp.zeros((), jnp.int32)
    point("empty", lambda acc, p: acc + p[0], zero, jnp.arange(B))

    # ---- the whole program: every key registered, then a batch of
    # uniform registrations (a device a handful of times at most)
    body = table_index.make_write(DTYPES, "deviceID", C, True)

    def write(state, cols, ts, live):
        return body(*state, cols, ts, live, {a: cols[a] for a in SETS})

    state = ({a: jnp.zeros((C,), d) for a, d in DTYPES.items()},
             jnp.zeros((C,), jnp.int64), jnp.zeros((C,), bool),
             table_index.init_index(C))
    fill = jax.jit(write, donate_argnums=(0,))
    for at in range(0, K, B):
        devices = np.arange(at, min(at + B, K))
        state = fill(state, *batch(np.pad(devices, (0, B - devices.size),
                                          mode="edge")))
    point("write.program", write, state,
          *batch(rng.integers(0, K, B)))

    # ---- the gathers alone, on the sort's outputs at that batch
    slot = jnp.asarray(rng.integers(0, K, B).astype(np.int32))
    s_slot, s_lane = lax.sort((slot, lax.iota(jnp.int32, B)), num_keys=2)
    edge = s_slot[1:] != s_slot[:-1]
    first = run_first(jnp.concatenate([jnp.ones((1,), bool), edge]))
    end = jnp.concatenate([edge, jnp.ones((1,), bool)])
    last = (B - 1) - run_first(end[::-1])[::-1]
    cols, ts, _ = batch(rng.integers(0, K, B))

    def fold(acc, *trees):
        for leaf in jax.tree_util.tree_leaves(trees):
            acc = acc + jnp.sum(leaf.astype(jnp.int32))
        return acc

    def per_column(acc, c, t, s_lane, first, last):
        lf, ll = s_lane[first], s_lane[last]
        return fold(acc, {k: v[lf] for k, v in c.items()}, t[lf],
                    {k: c[k][ll] for k in SETS})

    def index_then_rows(acc, c, t, s_lane, first, last):
        lf, ll = s_lane[first], s_lane[last]
        return fold(acc, gather_lanes((c, t), lf),
                    gather_lanes({k: c[k] for k in SETS}, ll))

    def rows_then_runs(acc, c, t, s_lane, first, last, gather=gather_lanes):
        s_c, s_t, s_u = gather((c, t, {k: c[k] for k in SETS}), s_lane)
        return fold(acc, s_c, gather(({"deviceID": s_c["deviceID"]}, s_t),
                                     first),
                    gather(s_u, last))

    def index_then_words(acc, c, t, s_lane, first, last):
        lf, ll = s_lane[first], s_lane[last]
        return fold(acc, words_major((c, t), lf),
                    words_major({k: c[k] for k in SETS}, ll))

    for name, fn in (
            ("gathers.per_column", per_column),
            ("gathers.index_then_rows", index_then_rows),
            ("gathers.rows_then_runs", rows_then_runs),
            ("gathers.index_then_words_major", index_then_words),
            ("gathers.words_major_then_runs",
             partial(rows_then_runs, gather=words_major))):
        point(name, fn, zero, cols, ts, s_lane, first, last)
    point("gather.word", lambda acc, c, i: acc + jnp.sum(c[i]), zero,
          cols["roomNo"], s_lane)
    point("gather.int64", lambda acc, c, i: acc + jnp.sum(c[i]),
          jnp.zeros((), jnp.int64), cols["updated"], s_lane)
    for name, gather in (("rows[B,12]", gather_lanes),
                         ("words_major[12,B]", words_major)):
        point(f"gather.{name}", lambda acc, c, t, i, g=gather: fold(
            acc, g((c, t, {k: c[k] for k in SETS}), i)),
              zero, cols, ts, s_lane)
        point(f"gather.{name}.at_run_first", lambda acc, c, t, i, g=gather:
              fold(acc, g((c, t, {k: c[k] for k in SETS}), i)),
              zero, cols, ts, first)

    # ---- the run scans, and a word scatter into the table's rows
    start = jnp.concatenate([jnp.ones((1,), bool), edge])
    point("scan.run_first", lambda acc, s: acc + jnp.sum(run_first(s)),
          zero, start)
    point("scan.cummax", lambda acc, s: acc + jnp.sum(lax.cummax(
        jnp.where(s, lax.iota(jnp.int32, B), 0))), zero, start)
    point("scan.cummin_reverse", lambda acc, e: acc + jnp.sum(lax.cummin(
        jnp.where(e, lax.iota(jnp.int32, B), B - 1), reverse=True)),
          zero, end)
    column = jnp.zeros((C,), jnp.int32)
    for sorted_ in (True, False):
        point(f"scatter.word.sorted_hint={sorted_}",
              lambda col, at, v, h=sorted_: col.at[at].set(
                  v, mode="drop", indices_are_sorted=h),
              column, s_slot, cols["roomNo"])


if __name__ == "__main__":
    main()
