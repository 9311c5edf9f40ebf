"""The selector in rounds over a sliding window's chunk: where
`@capacity(expire=...)` makes the chunk wider than what arrives and leaves
in a usual step, a lane-sequential selector runs in rounds of one batch over
the chunk's live prefix (`CompiledSelector.step_in_rounds`, chosen in
`core/query_runtime.py` `selector_round_width`). Every row and the final
selector state equal one call over the whole chunk, bit for bit; whatever
reads the chunk as a whole keeps the one call and the program it had; the
`selector_lanes` statistic counts the lanes the selector ran over.
"""

import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from siddhi_tpu import SiddhiManager, compiler
from siddhi_tpu.core import query_runtime as QR
from siddhi_tpu.core.event import EventBatch, EventType
from siddhi_tpu.io import wire
from siddhi_tpu.ops.expr_compile import Scope

B = 64  # the batch, and the width of a round
E = 4 * B  # what may leave the window in one step
L = B + E  # the chunk
SYMBOLS = 20
APP = """
@app:name('Rounds')
@app:playback
@Async(buffer.size='{batch}', workers='2')
define stream S (symbol string, price float, volume long, timestamp long);
@info(name = 'q')
{capacity}
from S#window.{window}
{select}
insert into O;
"""
EXPIRE = f"@capacity(window='1024', expire='{E}')"

# selectors that run in rounds
ROUNDS = {
    "distinct": "select timestamp, distinctCount(symbol) as d",
    "grouped_sum_count":
        "select symbol, sum(volume) as s, count() as n group by symbol",
    "ungrouped_sum_distinct":
        "select sum(volume) as s, count() as n, distinctCount(symbol) as d",
    "having": "select symbol, count() as n, sum(volume) as s "
              "group by symbol having n > 2",
}
# queries that keep one call over the whole chunk: (window, capacity, select)
ONE_CALL = {
    "order_by_limit": ("time(1 sec)", EXPIRE,
                       "select symbol, count() as n group by symbol "
                       "order by n desc limit 3"),
    "extrema": ("time(1 sec)", EXPIRE, "select max(price) as m"),
    "expire_is_the_batch": ("time(1 sec)",
                            f"@capacity(window='1024', expire='{B}')",
                            ROUNDS["distinct"]),
    "length_batch": (f"lengthBatch({B})", "", ROUNDS["grouped_sum_count"]),
    "float_sum": ("time(1 sec)", EXPIRE,
                  "select symbol, avg(price) as a group by symbol"),
    "grouped_distinct": ("time(1 sec)", EXPIRE,
                         "select symbol, distinctCount(volume) as d "
                         "group by symbol"),
    "key_table": ("time(1 sec)", EXPIRE,
                  "select volume, count() as n group by volume"),
    "sketch": ("time(1 sec)", EXPIRE,
               "select hll:distinctCount(symbol) as d"),
}


def app_text(select, window="time(1 sec)", capacity=EXPIRE):
    return APP.format(batch=B, capacity=capacity, window=window,
                      select=select)


class Deployment:
    def __init__(self, text: str) -> None:
        self.rt = SiddhiManager().create_siddhi_app_runtime(
            text, batch_size=B, group_capacity=4096, async_callbacks=True)
        self.blocks: list = []
        self.rt.add_callback("O", self.blocks.append, columnar=True)
        self.rt.start()
        self.plan = wire.schema_plan(compiler.parse(text)
                                     .stream_definitions["S"])
        self.handler = self.rt.get_input_handler("S")
        self.qr = self.rt.query_runtimes["q"]

    def send(self, stamps, seed: int) -> None:
        """One frame, run before the next is sent: one step a frame."""
        stamps = np.asarray(stamps, np.int64)
        n = stamps.size
        rng = np.random.default_rng([7, seed])
        symbols = [f"S{k:03d}" for k in rng.integers(0, SYMBOLS, n)]
        cols = {"symbol": np.array(symbols, dtype=object),
                "price": rng.integers(1, 400, n).astype(np.float32) / 4,
                "volume": rng.integers(1, 1000, n).astype(np.int64),
                "timestamp": stamps}
        body = wire.encode_frames(self.plan, cols, n, ts=stamps)
        assert wire.deliver_frames(self.handler, body) == n
        self.rt.drain()

    def rows(self) -> dict:
        self.rt.drain()
        names = list(self.blocks[0].columns)
        out = {"ts": np.concatenate([b.timestamps for b in self.blocks])}
        for n in names:
            out[n] = np.concatenate([b.column(n) for b in self.blocks])
        return out

    def close(self) -> None:
        self.rt.shutdown()


@pytest.fixture
def deploy():
    made = []

    def make(text):
        made.append(Deployment(text))
        return made[-1]
    yield make
    for d in made:
        d.close()


def frames(d: Deployment, which) -> None:
    """Frames four ticks a row apart, 300 apart: about a frame leaves each
    step; frame 20 makes the clock jump, so all that is live leaves at once
    and the chunk is full."""
    for f in which:
        d.send(f * 300 + 4 * np.arange(B), f)


def same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def same_rows(a: dict, b: dict) -> None:
    assert set(a) == set(b)
    for k in a:
        assert same_bits(a[k], b[k]), k


# ------------------------------------ rows and state equal the one call


def _scope(chunk) -> Scope:
    s = Scope()
    s.add_frame("S", chunk.cols, chunk.ts, chunk.valid, default=True)
    s.extras["now"] = jnp.int64(0)
    return s


@pytest.fixture(scope="module")
def selectors():
    """query -> (selector, one-call step, round step), jitted once."""
    made, runtimes = {}, []
    for name, select in ROUNDS.items():
        rt = SiddhiManager().create_siddhi_app_runtime(
            app_text(select), batch_size=B, group_capacity=4096)
        runtimes.append(rt)
        qr = rt.query_runtimes["q"]
        sel, ref = qr.selector, qr.frame_ref

        assert ref == "S"
        one = jax.jit(lambda st, ch, sel=sel: sel.step(st, ch, _scope(ch)))
        rounds = jax.jit(lambda st, ch, sel=sel: sel.step_in_rounds(
            st, ch, _scope(ch), B))
        made[name] = (sel, one, rounds)
    yield made
    for rt in runtimes:
        rt.shutdown()


def chunk_of(n_live: int, seed: int) -> EventBatch:
    """A chunk as the window leaves it: rows in front, the lane n_live - 1
    the last valid one, a few invalid lanes among the rows (a post-window
    filter's), and stale columns behind."""
    rng = np.random.default_rng(seed)
    valid = (np.arange(L) < n_live) & (rng.random(L) > 0.1)
    if n_live:
        valid[n_live - 1] = True
    types = np.where(rng.random(L) < 0.4, EventType.EXPIRED,
                     EventType.CURRENT).astype(np.int8)
    cols = {"symbol": rng.integers(0, SYMBOLS, L).astype(np.int32),
            "price": (rng.integers(1, 400, L) / 4).astype(np.float32),
            "volume": rng.integers(1, 1000, L).astype(np.int64),
            "timestamp": rng.integers(0, 10 ** 6, L).astype(np.int64)}
    return EventBatch(ts=jnp.asarray(cols["timestamp"]),
                      cols={k: jnp.asarray(v) for k, v in cols.items()},
                      valid=jnp.asarray(valid), types=jnp.asarray(types))


LIVE = {"none": 0, "round_less_one": B - 1, "one_round": B,
        "round_and_one": B + 1, "two_rounds_and_one": 2 * B + 1,
        "whole_chunk": L}


@pytest.mark.parametrize("live", LIVE, ids=list(LIVE))
@pytest.mark.parametrize("query", ROUNDS, ids=list(ROUNDS))
def test_rounds_give_the_one_calls_rows_and_state(selectors, query, live):
    sel, one, rounds = selectors[query]
    assert sel.lane_sequential
    state = sel.init_state()
    for seed in range(3):  # a state that holds something
        state, _ = one(state, chunk_of(L, 100 + seed))
    chunk = chunk_of(LIVE[live], 7)
    s1, o1 = one(state, chunk)
    s2, o2, lanes = rounds(state, chunk)
    assert int(lanes) == max(1, -(-LIVE[live] // B)) * B
    l1, l2 = jax.tree.leaves(s1), jax.tree.leaves(s2)
    assert len(l1) == len(l2)
    assert all(same_bits(a, b) for a, b in zip(l1, l2))
    assert same_bits(o1.valid, o2.valid)
    assert same_bits(o1.ts, o2.ts) and same_bits(o1.types, o2.types)
    keep = np.asarray(o1.valid)
    assert set(o1.cols) == set(o2.cols)
    for k in o1.cols:
        assert same_bits(np.asarray(o1.cols[k])[keep],
                         np.asarray(o2.cols[k])[keep]), k


@pytest.mark.parametrize("width", [48, 100, L])
def test_a_round_width_that_does_not_divide_the_chunk_pads_it(selectors,
                                                              width):
    sel, one, _ = selectors["ungrouped_sum_distinct"]
    rounds = jax.jit(lambda st, ch: sel.step_in_rounds(
        st, ch, _scope(ch), width))
    state = sel.init_state()
    for n_live in (L, 2 * B + 1, 0):
        chunk = chunk_of(n_live, n_live)
        s1, o1 = one(state, chunk)
        s2, o2, lanes = rounds(state, chunk)
        assert int(lanes) == max(1, -(-n_live // width)) * width
        assert all(same_bits(a, b) for a, b in zip(jax.tree.leaves(s1),
                                                   jax.tree.leaves(s2)))
        keep = np.asarray(o1.valid)
        assert same_bits(keep, o2.valid) and o2.capacity == L
        for k in o1.cols:
            assert same_bits(np.asarray(o1.cols[k])[keep],
                             np.asarray(o2.cols[k])[keep]), k
        state = s1


# ---------------------------------- where the one call and its program stay


def step_jaxpr(qr) -> str:
    batch = EventBatch.empty(qr.input_junction.definition, qr._batch_cap)
    return str(jax.make_jaxpr(qr._make_step(track_compiles=False))(
        qr.state, batch, jnp.int64(0), {}))


@pytest.mark.parametrize("query", ONE_CALL, ids=list(ONE_CALL))
def test_what_reads_the_chunk_whole_keeps_one_call_and_its_program(
        deploy, monkeypatch, query):
    window, capacity, select = ONE_CALL[query]
    qr = deploy(app_text(select, window, capacity)).qr
    assert QR.selector_round_width(qr.window, qr.selector, {}, L) is None
    kept = step_jaxpr(qr)
    assert "while[" not in kept
    monkeypatch.setattr(QR, "selector_round_width", lambda *a: None)
    assert step_jaxpr(qr) == kept


@pytest.mark.parametrize("query", ROUNDS, ids=list(ROUNDS))
def test_a_wide_expiry_runs_the_selector_in_a_loop(deploy, monkeypatch,
                                                   query):
    qr = deploy(app_text(ROUNDS[query])).qr
    assert QR.selector_round_width(qr.window, qr.selector, {}, L) == B
    rounds = step_jaxpr(qr)
    assert "while[" in rounds
    monkeypatch.setattr(QR, "selector_round_width", lambda *a: None)
    assert "while[" not in step_jaxpr(qr)


def test_one_row_per_group_and_a_per_lane_extra_keep_one_call(deploy):
    qr = deploy(app_text(ROUNDS["grouped_sum_count"])).qr
    final = copy.copy(qr.selector)
    final.__dict__.pop("lane_sequential", None)
    final.emit_final_per_group = True
    assert not final.lane_sequential
    assert QR.selector_round_width(qr.window, final, {}, L) is None
    lanes = {"extrema:m": jnp.zeros((L,), jnp.float32)}
    assert QR.selector_round_width(qr.window, qr.selector, lanes, L) is None
    assert QR.selector_round_width(
        qr.window, qr.selector, {"now": jnp.int64(0)}, L) == B


# ------------------------------------------------- through the runtime


@pytest.mark.parametrize("query", ROUNDS, ids=list(ROUNDS))
def test_the_served_rows_equal_the_one_calls(deploy, monkeypatch, query):
    which = list(range(6)) + [20, 21]
    rounds = deploy(app_text(ROUNDS[query]))
    frames(rounds, which)
    monkeypatch.setattr(QR, "selector_round_width", lambda *a: None)
    whole = deploy(app_text(ROUNDS[query]))
    frames(whole, which)
    same_rows(rounds.rows(), whole.rows())
    r = rounds.rt.statistics_report()["windows"]["q"]
    w = whole.rt.statistics_report()["windows"]["q"]
    assert r["out_lanes"] == w["out_lanes"] == w["selector_lanes"]
    assert r["selector_lanes"] < r["out_lanes"]


@pytest.mark.parametrize("query", ["distinct", "grouped_sum_count"])
def test_a_snapshot_between_round_steps_restores_the_same_rows(deploy,
                                                               query):
    text = app_text(ROUNDS[query])
    straight = deploy(text)
    frames(straight, range(8))
    first = deploy(text)
    frames(first, range(4))
    blob = first.rt.snapshot()
    second = deploy(text)
    second.rt.restore(blob)
    frames(second, range(4, 8))
    want = {k: v[4 * B:] for k, v in straight.rows().items()}
    same_rows(second.rows(), want)


SEQUENCES = {
    # frame sizes, 2000 ticks apart with one stamp a frame: a step takes
    # the frame before out and this one in
    "full_frames": [B, B, B],
    "ragged": [1, B, B - 1, 2],
    "one_row": [1],
}


@pytest.mark.parametrize("sizes", SEQUENCES.values(), ids=list(SEQUENCES))
def test_selector_lanes_count_the_rounds(deploy, sizes):
    d = deploy(app_text(ROUNDS["distinct"]))
    for f, n in enumerate(sizes):
        d.send(np.full(n, 2000 * f), f)
    live = [n + (sizes[f - 1] if f else 0) for f, n in enumerate(sizes)]
    report = d.rt.statistics_report()["windows"]["q"]
    assert report["expired"] == sum(sizes[:-1])
    assert report["selector_lanes"] == sum(
        max(1, -(-n // B)) * B for n in live)


def test_one_call_counts_the_chunks_width(deploy):
    d = deploy(app_text(ROUNDS["distinct"],
                        capacity=f"@capacity(window='1024', expire='{B}')"))
    frames(d, range(3))
    report = d.rt.statistics_report()["windows"]["q"]
    assert report["selector_lanes"] == report["out_lanes"] == 3 * 2 * B
