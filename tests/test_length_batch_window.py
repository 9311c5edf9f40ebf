"""lengthBatch(N) on the packed ring against a plain per-event evaluation.

The reference below is LengthBatchWindowProcessor.java:210-243 read one event
at a time: an arrival joins the pending bucket; the arrival that fills it
emits [the previous bucket as EXPIRED, stamped with this arrival's time],
RESET, [the bucket as CURRENT, own stamps]. The op must give the same rows,
in the same order, with the same types and stamps, as a valid PREFIX of its
chunk, and `contents()` must hold exactly the pending bucket.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu.core.event import EventBatch, EventType
from siddhi_tpu.ops.windows import LengthBatchWindow

LAYOUT = {
    "sym": jnp.int32,
    "price": jnp.float32,
    "volume": jnp.int64,
    "ratio": jnp.float64,
    "flag": jnp.bool_,
}
NAMES = list(LAYOUT)


def _random_batch(rng, B, density, ts0):
    cols = {
        "sym": rng.integers(-2**31, 2**31 - 1, B, dtype=np.int32),
        "price": rng.standard_normal(B).astype(np.float32),
        "volume": rng.integers(-2**62, 2**62, B, dtype=np.int64),
        "ratio": rng.standard_normal(B),
        "flag": rng.random(B) < 0.5,
    }
    ts = ts0 + np.cumsum(rng.integers(0, 3, B)).astype(np.int64)
    valid = rng.random(B) < density
    # a few valid lanes of another type ride along and must be ignored
    types = np.where(rng.random(B) < 0.05, EventType.EXPIRED,
                     EventType.CURRENT).astype(np.int8)
    return cols, ts, valid, types


class _PerEvent:
    """lengthBatch(N), one event at a time."""

    def __init__(self, N, expired_on):
        self.N, self.expired_on = N, expired_on
        self.pending, self.prev = [], []

    def step(self, cols, ts, valid, types):
        out = []  # (type, ts, row or None)
        for i in np.flatnonzero(valid & (types == EventType.CURRENT)):
            self.pending.append(
                (int(ts[i]), tuple(cols[n][i] for n in NAMES)))
            if len(self.pending) < self.N:
                continue
            t = int(ts[i])
            if self.expired_on:
                out += [(EventType.EXPIRED, t, row) for _, row in self.prev]
            out.append((EventType.RESET, t, None))
            out += [(EventType.CURRENT, ets, row) for ets, row in self.pending]
            self.prev, self.pending = self.pending, []
        return out


def _bits(x):
    """Compare payloads bit for bit (floats included)."""
    x = np.asarray(x)
    return x.view({4: np.uint32, 8: np.uint64}.get(x.dtype.itemsize, x.dtype)) \
        if x.dtype.kind == "f" else x


def _row_at(cols, i):
    return tuple(_bits(cols[n])[i] for n in NAMES)


def _ref_row(row):
    return tuple(_bits(np.asarray(v))[()] for v in row)


@pytest.mark.parametrize("expired_on", [False, True], ids=["current", "all"])
@pytest.mark.parametrize("B,N", [(64, 10), (32, 32), (16, 50), (32, 1)],
                         ids=["several_flushes", "n_eq_b", "n_gt_b", "n_1"])
def test_length_batch_matches_per_event_evaluation(B, N, expired_on):
    win = LengthBatchWindow(LAYOUT, B, N, expired_on=expired_on)
    ref = _PerEvent(N, expired_on)
    step = jax.jit(win.step)
    contents = jax.jit(win.contents)
    state = win.init_state()
    rng = np.random.default_rng(31_000 + 100 * B + 2 * N + expired_on)
    zero = _ref_row(tuple(np.zeros((), np.dtype(dt))
                          for dt in LAYOUT.values()))  # a RESET lane's row

    # enough arrivals for the ring (C = 2N + B lanes) to wrap twice; an empty
    # and a full batch among the random ones
    densities = [0.6, 0.0, 1.0]
    arrivals, ts0, k = 0, 1_000, 0
    while arrivals <= 2 * win.C + B or k < len(densities):
        density = densities[k] if k < len(densities) else rng.random()
        cols, ts, valid, types = _random_batch(rng, B, density, ts0)
        ts0 = int(ts[-1]) + 1
        k += 1
        arrivals += int((valid & (types == EventType.CURRENT)).sum())
        state, chunk = step(
            state,
            EventBatch(ts=jnp.asarray(ts),
                       cols={n: jnp.asarray(v) for n, v in cols.items()},
                       valid=jnp.asarray(valid), types=jnp.asarray(types)),
            jnp.int64(ts0))
        want = ref.step(cols, ts, valid, types)

        assert chunk.capacity == win.chunk_width
        got_valid = np.asarray(chunk.valid)
        n_out = int(got_valid.sum())
        assert got_valid[:n_out].all(), "valid lanes are not a prefix"
        assert n_out == len(want)
        got_cols = {n: np.asarray(chunk.cols[n]) for n in NAMES}
        assert {n: a.dtype for n, a in got_cols.items()} == \
            {n: np.dtype(dt) for n, dt in LAYOUT.items()}
        got_types = np.asarray(chunk.types)
        got_ts = np.asarray(chunk.ts)
        assert got_types.dtype == np.int8 and got_ts.dtype == np.int64
        assert [int(t) for t in got_types[:n_out]] == \
            [int(t) for t, _, _ in want]
        assert [int(t) for t in got_ts[:n_out]] == [t for _, t, _ in want]
        for lane, (_, _, row) in enumerate(want):
            assert _row_at(got_cols, lane) == \
                (zero if row is None else _ref_row(row)), lane

        # contents(): exactly the pending bucket, at its ring slots
        c_cols, c_ts, live = contents(state, jnp.int64(ts0))
        c_cols = {n: np.asarray(c_cols[n]) for n in NAMES}
        c_ts, live = np.asarray(c_ts), np.asarray(live)
        assert live.shape == (win.C,)
        assert int(live.sum()) == len(ref.pending)
        flushed = int(state.flushed)
        assert int(state.appended) - flushed == len(ref.pending)
        for off, (ets, row) in enumerate(ref.pending):
            slot = (flushed + off) % win.C
            assert live[slot]
            assert int(c_ts[slot]) == ets
            assert _row_at(c_cols, slot) == _ref_row(row)
    assert int(state.appended) > 2 * win.C


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("expired_on", [False, True], ids=["current", "all"])
def test_step_moves_rows_as_words_and_never_scatters_onto_the_ring(expired_on):
    """Pins the mechanism: the ring is appended by slices (no scatter lands
    on a ring-shaped array), and no gather or scatter moves an 8-byte
    element (an int64 scatter is emulated on the TPU: 12x a word's)."""
    B, N = 256, 100
    win = LengthBatchWindow(LAYOUT, B, N, expired_on=expired_on)
    batch = EventBatch(
        ts=jnp.zeros((B,), jnp.int64),
        cols={n: jnp.zeros((B,), dt) for n, dt in LAYOUT.items()},
        valid=jnp.ones((B,), bool), types=jnp.zeros((B,), jnp.int8))
    jaxpr = jax.make_jaxpr(win.step)(win.init_state(), batch, jnp.int64(0))
    moves = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "gather"
             or e.primitive.name.startswith("scatter")]
    assert moves, "the compaction's partition and gather should be here"
    for e in moves:
        operand = e.invars[0].aval
        assert operand.dtype.itemsize <= 4, (e.primitive.name, operand)
        if e.primitive.name.startswith("scatter"):
            assert win.C not in operand.shape, (e.primitive.name, operand)
    # compaction: one partition scatter, one packed gather; emission: one
    # packed gather
    assert len(moves) <= 4, [str(e.primitive) for e in moves]
