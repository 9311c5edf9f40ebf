#!/usr/bin/env python
"""Where the merge overtakes the search: `ops/search.py` `rank_sorted32`
picks one of two algorithms from the static shapes `(N, B)`, and this prints
what each costs on the device it runs on, so that the rule can be measured
again on another chip.

    python tools/rank_crossover.py [--points N:B,N:B,...] [--reps 20]

One JSON line a point: `n`, `b`, `search_ms`, `merge_ms` (the median over
`--reps` executions of each, timed around `block_until_ready`), `picked`
(what `rank_sorted32` takes at that shape) and `equal` (both gave
`numpy.searchsorted`'s answer on sorted int64 stamps with ties). The first
line names the device; a time from the CPU backend is not a device number.
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

import siddhi_tpu  # noqa: E402,F401 — turns 64-bit types on
from siddhi_tpu.ops import search  # noqa: E402

# the points of `_merge_beats_search`'s docstring and PERF.md (PR 35)
POINTS = ("524288:131072,524288:16384,524288:8192,524288:4096,524288:1024,"
          "131072:131072,131072:4096,131072:1024,16384:16384,16384:1024,"
          "16384:128,1024:1024,1024:64")


def _timed(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def measure(n: int, b: int, reps: int) -> dict:
    rng = np.random.default_rng(0)
    # stamps a tick apart on average, with ties, above bit 32
    a = np.sort(rng.integers(0, n + b, n)) + (1 << 40)
    v = np.sort(rng.integers(0, n + b, b)) + (1 << 40)
    want = np.searchsorted(a, v, side="right")
    args = (jnp.asarray(a, jnp.int64), jnp.asarray(v, jnp.int64))

    out = {"n": n, "b": b}
    equal = True
    for name, fn in (("search_ms", search.searchsorted32),
                     ("merge_ms", search._merge_ranks)):
        jitted = jax.jit(functools.partial(fn, side="right"))
        equal = equal and bool((np.asarray(jitted(*args)) == want).all())
        out[name] = _timed(jitted, args, reps)
    out["picked"] = "merge" if search._merge_beats_search(n, b) else "search"
    out["equal"] = equal
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--points", default=POINTS)
    ap.add_argument("--reps", type=int, default=20)
    ns = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}))
    ok = True
    for point in ns.points.split(","):
        n, b = (int(x) for x in point.split(":"))
        row = measure(n, b, ns.reps)
        ok = ok and row["equal"]
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
