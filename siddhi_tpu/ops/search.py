"""Sort and search primitives the window, group-by and join steps share:
a stable partition by prefix sums, the one stable argsort of bounded keys
(`stable_argsort_bounded` — the only place that decides how such a key is
sorted), and a branchless unrolled binary search.

XLA lowers `jnp.searchsorted` to a `while` HLO whose per-iteration dispatch
dominated sliding-window steps on TPU (profiled at ~50% of step time: the
loop body runs as 2 small fusions x log2(N) iterations with loop overhead
between each). A static unroll of the same log2(N) halving steps compiles to
straight-line vector code XLA fuses into neighbouring ops.

Semantics match `jnp.searchsorted(a, v, side=...)` for a sorted 1-D `a`,
returning int32 (positions are lane indices; int64 lane math is emulated on
TPU — see ops/windows.py)."""

from __future__ import annotations

import math

import jax.numpy as jnp


def stable_partition_order(live):
    """Permutation that stably moves live lanes to the front — two prefix
    sums + one scatter instead of a sort. XLA CPU's comparator sort is
    ~50x slower than its cumsum at the same width (74 ms vs 1.4 ms at 282k
    lanes, measured); on TPU the scatter form also beats bitonic argsort.
    Replaces the `argsort(~live, stable=True)` idiom everywhere."""
    n = live.shape[0]
    live_i = live.astype(jnp.int32)
    pos_live = jnp.cumsum(live_i) - 1
    n_live = jnp.sum(live_i)
    pos_dead = n_live + jnp.cumsum(1 - live_i) - 1
    dest = jnp.where(live, pos_live, pos_dead)
    iota = jnp.arange(n, dtype=jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[dest].set(iota)


#: lane count from which the CPU backend sorts one packed (key, lane) word
#: instead of calling `jnp.argsort`: XLA CPU's comparator co-sort of (key,
#: iota) loses to a single-operand sort of one int64 from about this width
#: (16,384 lanes: 6.9 ms vs 1.4 ms; 131,072 lanes: 59.1 ms vs 12.7 ms).
_PACKED_SORT_MIN_LANES = 8192


def stable_argsort_bounded(x):
    """Stable argsort of NON-NEGATIVE int32 keys, as int32 positions.

    Below `_PACKED_SORT_MIN_LANES`, and on every backend but the CPU at any
    width: `jnp.argsort(stable=True)` (int64 lane math is emulated on TPU,
    so the packed word would cost more there than the co-sort it saves).
    From that width on the CPU: pack `(key << 32) | lane` into one int64
    word and run a SINGLE unstable single-operand `lax.sort` — the lane
    index in the low bits makes the order stable by construction and the
    low 32 bits of the sorted words ARE the argsort. Keys are bounded
    (< 2^31), so the shifted word never overflows int64. Both arms stay
    on the device: the compiled step keeps pjit's C++ fastpath and can
    ride inside a superstep `lax.scan` (core/superstep.py)."""
    from jax import lax

    def default_fn(v):
        return jnp.argsort(v, axis=-1, stable=True).astype(jnp.int32)

    def packed_fn(v):
        lane = lax.broadcasted_iota(jnp.int64, v.shape, v.ndim - 1)
        packed = (v.astype(jnp.int64) << 32) | lane
        swords = lax.sort(packed, dimension=v.ndim - 1, is_stable=False)
        return (swords & jnp.int64(0xFFFFFFFF)).astype(jnp.int32)

    if x.shape[-1] < _PACKED_SORT_MIN_LANES:
        return default_fn(x)
    return lax.platform_dependent(x, cpu=packed_fn, default=default_fn)


def searchsorted32(a, v, side: str = "left"):
    """Positions where `v` would insert into sorted `a`, as int32.

    a: sorted [N]; v: any shape. side='left' counts elements < v,
    side='right' counts elements <= v — same as jnp.searchsorted.
    """
    N = a.shape[0]
    pos = jnp.zeros(jnp.shape(v), jnp.int32)
    if N == 0:
        return pos
    bits = max(1, math.ceil(math.log2(N + 1)))
    for shift in range(bits - 1, -1, -1):
        step = jnp.int32(1 << shift)
        cand = pos + step
        probe = a[jnp.clip(cand - 1, 0, N - 1)]
        ok = probe < v if side == "left" else probe <= v
        pos = jnp.where((cand <= N) & ok, cand, pos)
    return pos
