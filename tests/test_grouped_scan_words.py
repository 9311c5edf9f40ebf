"""`ops/groupby.grouped_scan` and `grouped_scan_fused` against a per-event
dict, over tables of every width the engine keeps (8-byte columns cross a
scatter as two 32-bit words: `ops/lanes.scatter_lanes`), and the lowered
program as the evidence that the split engages: no scatter of 8-byte
elements is left in either function.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from siddhi_tpu.ops import groupby as G

K, L = 64, 96
DTYPES = [jnp.int64, jnp.float64, jnp.float32, jnp.int32]
COMBINE = {"sum": lambda a, b: a + b, "min": min, "max": max}


def identity(op: str, dtype):
    if op == "sum":
        return 0
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return info.max if op == "min" else info.min
    return float("inf") if op == "min" else float("-inf")


def make_batch(dtype, seed: int, reset_at: int | None, keys: int = 12):
    """Few keys so every one repeats; a fifth of the lanes invalid (their
    slots anything, in range or not); deltas exact in `dtype` whatever the
    order they are added in, and for an 8-byte table wide enough that both
    words of a running value change."""
    rng = np.random.default_rng([33, seed])
    slots = rng.integers(0, keys, L).astype(np.int32)
    valid = rng.random(L) > 0.2
    slots[~valid] = rng.integers(-5, K + 5, int((~valid).sum()))
    wide = jnp.dtype(dtype).itemsize == 8
    deltas = rng.integers(-2**40, 2**40, L) if wide \
        else rng.integers(-1000, 1000, L)
    if wide:
        # key 3: up to the word boundary, across it, back, then below zero
        # (a carry into the high word, a borrow out of it, a sign change)
        walk = [2**32 - 1, 1, -2, -2**33, 2**34 + 5]
        lanes = np.flatnonzero(valid)[5:5 + len(walk)]
        slots[lanes] = min(3, keys - 1)
        deltas[lanes] = walk
    resets = np.zeros(L, bool)
    if reset_at is not None:
        resets[reset_at] = True
    return slots, np.asarray(deltas, np.dtype(dtype)), valid, resets


class PerEvent:
    """The semantics in words: one event at a time, a reset lane first
    clears every group, a valid lane then folds its delta into its key's
    accumulator and reads it back."""

    def __init__(self, op: str, dtype) -> None:
        self.op, self.zero, self.acc = COMBINE[op], identity(op, dtype), {}

    def batch(self, slots, deltas, valid, resets) -> list:
        out = []
        for s, d, v, r in zip(slots.tolist(), deltas.tolist(),
                              valid.tolist(), resets.tolist()):
            if r:
                self.acc = {}
            if v:
                self.acc[s] = self.op(self.acc.get(s, self.zero), d)
                out.append(self.acc[s])
        return out


def run_two_batches(step, op: str, dtype, keys: int = 12):
    """Two batches through `step(state..., batch, epoch)`, the second with a
    reset in its middle: the first batch's table is read back as the
    second's carry-in, word for word."""
    ref = PerEvent(op, dtype)
    epoch = 1  # above the fresh table's 0: its zeros read as the identity
    state = None
    for seed, reset_at in ((1, None), (2, L // 2)):
        slots, deltas, valid, resets = make_batch(dtype, seed, reset_at,
                                                  keys)
        state, out = step(state, jnp.asarray(slots), jnp.asarray(deltas),
                          jnp.asarray(valid), jnp.asarray(resets),
                          jnp.int32(epoch))
        assert out.dtype == jnp.dtype(dtype)
        want = ref.batch(slots, deltas, valid, resets)
        assert np.asarray(out)[valid].tolist() == want
        epoch += int(resets.sum())
    return state, ref, epoch


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("fn", ["sum", "min", "max", "fused", "ungrouped_sum",
                                "ungrouped_min", "ungrouped_fused"])
def test_grouped_scans_equal_a_per_event_dict(fn, dtype):
    """The ungrouped scans are the same semantics with every slot 0 and a
    table of one cell."""
    grouped, is_fused = not fn.startswith("ungrouped_"), fn.endswith("fused")
    op = "sum" if is_fused else fn.removeprefix("ungrouped_")
    cells = K if grouped else 1

    def scan(state, slots, deltas, valid, resets, epoch):
        state = state or G.init_group_state(cells, dtype)
        if grouped:
            return G.grouped_scan(state, slots, deltas, valid, resets, epoch,
                                  op=op)
        return G.ungrouped_scan(state, deltas, valid, resets, epoch, op=op)

    def fused(state, slots, deltas, valid, resets, epoch):
        # a second component of another width beside it, as sum() and
        # count() sit side by side in one query
        values, shared = state or (
            [jnp.zeros((cells,), dtype), jnp.zeros((cells,), jnp.int64)],
            jnp.zeros((cells,), jnp.int32))
        ones = jnp.ones((L,), jnp.int64)
        if grouped:
            values, shared, outs = G.grouped_scan_fused(
                values, shared, slots, [deltas, ones], valid, resets, epoch)
        else:
            values, shared, outs = G.ungrouped_scan_fused(
                values, shared, [deltas, ones], valid, resets, epoch)
        return (values, shared), outs[0]

    state, ref, epoch = run_two_batches(fused if is_fused else scan, op,
                                        dtype, keys=12 if grouped else 1)
    table, epochs = (state[0][0], state[1]) if is_fused \
        else (state.values, state.epoch)
    assert table.dtype == jnp.dtype(dtype) and table.shape == (cells,)
    live = np.asarray(epochs) == epoch
    assert sorted(np.flatnonzero(live).tolist()) == sorted(ref.acc)
    assert {k: np.asarray(table)[k].item() for k in ref.acc} == ref.acc


@pytest.mark.parametrize("seed", range(4))
def test_no_two_in_bounds_writes_share_a_slot(seed):
    """What lets an 8-byte column's words be scattered apart: the plan's
    `write_slot` names each slot at most once (every other lane carries the
    out-of-bounds sentinel), and `order` is a permutation."""
    slots, _, valid, resets = make_batch(jnp.int64, seed, L // 3)
    resets[2 * L // 3] = True
    plan = G._segment_plan(jnp.asarray(slots), jnp.asarray(valid),
                           jnp.asarray(resets), jnp.int32(7), K)
    write = np.asarray(plan.write_slot)
    inside = write[write < K]
    assert (write >= 0).all() and (write[write >= K] == K).all()
    assert len(set(inside.tolist())) == inside.size
    assert set(inside.tolist()) == {s for s, v in zip(slots, valid)
                                    if v and 0 <= s < K}
    assert sorted(np.asarray(plan.order).tolist()) == list(range(L))


def scatters_of(fn, *args) -> list:
    """Operand types of every `stablehlo.scatter` in the lowered program."""
    text = jax.jit(fn).lower(*args).as_text()
    found = re.findall(r'"stablehlo\.scatter"\(.*?\}\) : \((.*?)\) -> ', text,
                       flags=re.S)
    assert len(found) == text.count('"stablehlo.scatter"(')
    return found


def lowered_scatters(fn: str, dtype) -> list:
    slots, deltas, valid, resets = map(jnp.asarray, make_batch(dtype, 1, 5))
    if fn == "scan":
        return scatters_of(
            lambda *a: G.grouped_scan(G.GroupState(a[0], a[1]), *a[2:]),
            jnp.zeros((K,), dtype), jnp.zeros((K,), jnp.int32), slots,
            deltas, valid, resets, jnp.int32(0))
    return scatters_of(
        lambda v0, v1, e, *a: G.grouped_scan_fused(
            [v0, v1], e, a[0], [a[1], a[1]], *a[2:]),
        jnp.zeros((K,), dtype), jnp.zeros((K,), dtype),
        jnp.zeros((K,), jnp.int32), slots, deltas, valid, resets,
        jnp.int32(0))


@pytest.mark.parametrize("dtype", [jnp.int64, jnp.float64],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("fn, words", [("scan", 5), ("fused", 6)])
def test_no_8_byte_element_crosses_a_scatter(fn, words, dtype):
    """In place of an engagement counter (the split is chosen by dtype at
    trace time): the table write and the way back to lane order go as two
    word scatters each, the epoch table and the inverse permutation as one
    (before: 3 scatters in `grouped_scan`, two of them over i64)."""
    found = lowered_scatters(fn, dtype)
    assert len(found) == words
    assert not [t for t in found if re.search(r"x[if]64>", t)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("fn, scatters", [("scan", 3), ("fused", 4)])
def test_a_4_byte_table_takes_the_scatters_it_took(fn, scatters, dtype):
    assert len(lowered_scatters(fn, dtype)) == scatters
