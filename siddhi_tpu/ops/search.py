"""Sort and search primitives the window, group-by and join steps share:
a stable partition by prefix sums, the one stable argsort of bounded keys
(`stable_argsort_bounded` — the only place that decides how such a key is
sorted), and two ways to rank values in a sorted array.

**Which of the two searches a caller wants.** `searchsorted32(a, v)` takes
any `v`: ceil(log2(N + 1)) rounds, each a gather of one probe per element of
`v` out of `a`. The TPU gathers about 7 ns an index and word whatever the
operand's size, so it is the right one for a scalar or a narrow `v`, for an
unsorted one, and wherever `a` is wide and `v` is not; at 131,072 eight-byte
values in 524,288 it costs 38 ms (PERF.md, PR 35). `rank_sorted32(a, v)`
wants `v` SORTED as well (two running maxima, two prefix sums): the rank of
one sorted sequence in another is a merge, which a sort of the N + B keys
gives in 2.6 ms at those widths whatever B is. It picks the search again by
itself where the sort would cost more (a rung of 1,024 lanes against
524,288 deadlines), so a caller whose `v` is sorted asks for it and never
for the search. One caller today: the time rule of `SlidingWindow.step`.

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
from jax import lax


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


def rank_sorted32(a, v, side: str = "left"):
    """`searchsorted32(a, v, side)` for a SORTED 1-D `v`, by whichever of
    the search and the merge (`_merge_ranks`) is cheaper at the static
    shapes `(N, B)`; the answers are the same bit for bit."""
    (N,), (B,) = a.shape, v.shape
    if _merge_beats_search(N, B):
        return _merge_ranks(a, v, side)
    return searchsorted32(a, v, side)


def _merge_beats_search(n: int, b: int) -> bool:
    """The search gathers b indices in each of its rounds, the merge sorts
    n + b lanes whatever b is. Measured on a TPU v5 lite (PERF.md, PR 35:
    `tools/rank_crossover.py`, int64 keys, ms around `block_until_ready`,
    of which some 0.55 is the call's own; search / merge):

        n 524,288:  b 1,024 0.94 / 2.37   4,096 1.80 / 2.30   8,192 2.98 / 2.24
                    16,384 5.43 / 2.23    131,072 38.36 / 2.42
        n 131,072:  b 1,024 0.93 / 1.01   4,096 1.69 / 1.03   131,072 34.65 / 1.22
        n  16,384:  b 128 0.63 / 0.64     1,024 0.81 / 0.62   16,384 4.25 / 0.61

    which is 15 ns an index and round against 3.3 ns a sorted lane; the
    factor is 4 and not 4.5 because a sort also costs some 25 s more to
    compile for the TPU, on every rung of the bucket ladder that takes it."""
    return 4 * b * max(1, math.ceil(math.log2(n + 1))) > n + b


def _merge_ranks(a, v, side: str):
    """The rank of every element of one sorted sequence in another is a
    merge, not a search. One sort of the N + B keys — an 8-byte integer as
    its two words, the high one signed and the low one unsigned: exact,
    nothing is rebased — with a last key that says which sequence a lane
    came from and lays the one that wins a tie first (`a` for side='right':
    an `a` equal to a `v` counts). Both inputs are sorted, so the k-th `v`
    lane in merged order holds v[k], and its rank in `a` is its merged
    position less k. A second sort, of one key that puts the `v` lanes
    first in merged order, brings their positions to the front (a scatter
    would take all N + B lanes as updates: 2.5 ms more at 655,360). Every
    key is unique up to lanes that are interchangeable, so neither sort
    needs to be stable (two stable sorts here doubled the compile time of
    the whole step for the TPU: PERF.md, PR 35)."""
    (N,), (B,) = a.shape, v.shape
    keys = jnp.concatenate([a, v])
    if keys.dtype.itemsize == 8 and jnp.issubdtype(keys.dtype, jnp.integer):
        operands = ((keys >> 32).astype(jnp.int32), keys.astype(jnp.uint32))
    else:
        operands = (keys,)
    lane = lax.iota(jnp.int32, N + B)
    from_v = lane >= N
    loses_tie = from_v if side == "right" else ~from_v
    loses_tie = lax.sort(operands + (loses_tie.astype(jnp.int32),),
                         num_keys=len(operands) + 1, is_stable=False)[-1]
    from_v = (loses_tie > 0) == (side == "right")
    # the key of a `v` lane is its position already; it rides as a payload
    # too because XLA's TPU sort of a lone operand compiles twice as long
    v_first = jnp.where(from_v, lane, lane + (N + B))
    _, pos = lax.sort((v_first, lane), num_keys=1, is_stable=False)
    return pos[:B] - lax.iota(jnp.int32, B)
