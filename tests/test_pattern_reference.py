"""`every A -> B[B.key == A.key] within W` against its plain per-event
reference (tests/pattern_reference.py), through the normal path: SXF1 frames
-> `wire.deliver_frames` -> two `@Async` streams, each with its own
IngressPipeline -> the jitted pattern steps -> AsyncDecoder -> columnar
callback; and the step's match by key against the dense `[B, P]` mask it
replaces, bit for bit.

A frames yield no block, so the serialisation is read back from the rows:
a block's timestamps are its completing (B) events', every event's
timestamp is `frame * STRIDE + row`, and an A frame stands before the first
block that holds a row of it. The reference replays that order: rows, order
and bits must be equal.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, compiler
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.pattern_runtime import PatternQueryRuntime
from siddhi_tpu.errors import SiddhiAppCreationError
from siddhi_tpu.io import wire

from .pattern_reference import A, B, EveryAThenB

STREAMS = ("cseEventStream", "quoteEventStream")
STRIDE = 10000  # event indexes per frame number; frames are smaller
APP = """
@app:name('Pattern{batch}x{pending}')
@app:playback
@Async(buffer.size='{batch}', workers='2')
define stream cseEventStream   (symbol string, price float, volume long, timestamp long);
@Async(buffer.size='{batch}', workers='2')
define stream quoteEventStream (symbol string, price float, volume long, timestamp long);
@info(name = 'pattern') @capacity(pending='{pending}')
from every t=cseEventStream -> q=quoteEventStream[{condition}] within {within} sec
select t.symbol as symbol, t.price as tradePrice, q.price as quotePrice,
       t.timestamp as tradeStamp, q.timestamp as quoteStamp
insert into matchedStream;
"""
KEYED = "q.symbol == t.symbol"
WITHIN_S = 5000  # 5,000,000 ticks: 500 frame numbers here


class Deployment:
    """One runtime of the pattern and, beside it, the per-event reference
    fed the same frames in the order the engine ran them."""

    def __init__(self, batch: int, pending: int, within_s: int = WITHIN_S,
                 condition: str = KEYED) -> None:
        text = APP.format(batch=batch, pending=pending, within=within_s,
                          condition=condition)
        self.batch = batch
        self.rt = SiddhiManager().create_siddhi_app_runtime(
            text, batch_size=batch, async_callbacks=True)
        self.blocks: list = []
        self.rt.add_callback("matchedStream", self.blocks.append,
                             columnar=True)
        self.rt.start()
        definitions = compiler.parse(text).stream_definitions
        self.plans = [wire.schema_plan(definitions[s]) for s in STREAMS]
        self.handlers = [self.rt.get_input_handler(s) for s in STREAMS]
        self.sent: dict = {}
        self.reference = EveryAThenB(within_s * 1000)
        self.frames = 0  # frame numbers handed out
        self.settled = 0  # blocks compared so far

    @property
    def query(self) -> PatternQueryRuntime:
        return self.rt.query_runtimes["pattern"]

    def numbers(self, n: int) -> list:
        self.frames += n
        return list(range(self.frames - n, self.frames))

    def send(self, side: int, f: int, keys) -> None:
        """Frame `f` on `side`: one event per key, stamped with its global
        index both as its timestamp and in its `timestamp` attribute."""
        keys = np.asarray(keys)
        n = keys.size
        assert n <= STRIDE
        prices = (np.random.default_rng([11, f]).integers(1, 4000, n)
                  * 0.25).astype(np.float32)
        index = f * STRIDE + np.arange(n, dtype=np.int64)
        cols = {"symbol": np.array([f"S{k:05d}" for k in keys.tolist()],
                                   dtype=object),
                "price": prices, "volume": np.ones(n, np.int64),
                "timestamp": index}
        self.sent[f] = (side, keys, prices)
        body = wire.encode_frames(self.plans[side], cols, n, ts=index)
        assert wire.deliver_frames(self.handlers[side], body) == n

    def seeded(self, side: int, f: int, n_keys: int, seed: int = 7) -> None:
        self.send(side, f, np.random.default_rng([seed, f]).integers(
            0, n_keys, self.batch))

    def sweep(self, n_keys: int) -> list:
        """B frames over every key: nothing is left waiting for the next
        test (the deployments are shared). Returns their frame numbers."""
        keys = np.arange(n_keys)
        order = self.numbers(-(-n_keys // self.batch))
        for i, f in enumerate(order):
            self.send(B, f, keys[i * self.batch:(i + 1) * self.batch])
        self.rt.drain()
        return order

    def new_blocks(self) -> list:
        self.rt.drain()
        return self.blocks[self.settled:]

    def order_of(self, blocks, a_frames) -> list:
        """The serialisation, from the rows: B frames in block order, each
        A frame before the first block that holds a row of it (one sender
        sends them in number order; one that no row names stands last)."""
        first_block = {}
        b_frames = []
        for j, b in enumerate(blocks):
            mine = np.unique(b.timestamps // STRIDE)
            assert mine.size == 1, "a block answers one B frame"
            b_frames.append(int(mine[0]))
            for f in np.unique(b.column("tradeStamp") // STRIDE).tolist():
                first_block.setdefault(int(f), j)
        order = []
        for j, bf in enumerate(b_frames):
            order += [f for f in a_frames if first_block.get(f) == j]
            order.append(bf)
        return order + [f for f in a_frames if f not in first_block]

    def settle(self, order) -> tuple:
        """(rows delivered since the last settle, rows the per-event
        reference gives for the frames in `order`): each row (completing
        event's timestamp, symbol, tradePrice bits, quotePrice bits,
        tradeStamp, quoteStamp)."""
        blocks = self.new_blocks()
        self.settled = len(self.blocks)
        got = []
        for b in blocks:
            assert not b.is_expired.any()
            got.extend(zip(
                b.timestamps.tolist(), b.strings("symbol"),
                b.column("tradePrice").astype(np.float32).view(np.int32)
                .tolist(),
                b.column("quotePrice").astype(np.float32).view(np.int32)
                .tolist(),
                b.column("tradeStamp").tolist(),
                b.column("quoteStamp").tolist()))
        want = []
        for f in order:
            side, keys, prices = self.sent[f]
            bits = prices.view(np.int32).tolist()
            stamps = [f * STRIDE + i for i in range(keys.size)]
            pairs = self.reference.frame(
                side, keys.tolist(), stamps,
                [(s, bits[i], int(keys[i])) for i, s in enumerate(stamps)])
            want += [(q_idx, f"S{key:05d}", t_bits, q_bits, t_idx, q_idx)
                     for (t_idx, t_bits, key), (q_idx, q_bits, _) in pairs]
        return got, want

    def pattern_stats(self) -> dict:
        return self.rt.statistics_report()["patterns"]["pattern"]


SIZES = {"small": (64, 512), "rehearsal": (256, 2048)}
_deployments: dict = {}


def _deployment(name: str, *args, **kw) -> Deployment:
    if name not in _deployments:
        _deployments[name] = Deployment(*args, **kw)
    return _deployments[name]


@pytest.fixture(scope="module", autouse=True)
def _shut_down():
    yield
    for d in _deployments.values():
        d.rt.shutdown()
    _deployments.clear()


@pytest.fixture
def small():
    return _deployment("small", *SIZES["small"])


# -------------------------------------------------- engine == reference


def _alternating(d: Deployment, n_keys: int, seed: int) -> list:
    order = d.numbers(12)
    for i, f in enumerate(order):
        d.seeded(i % 2, f, n_keys, seed)
        d.rt.drain()  # fixes the order across the two streams' feeders
    return order


def _runs_of_a(d: Deployment, n_keys: int, seed: int) -> list:
    """Runs of up to four A frames before a B (four closed-loop clients a
    stream), and B frames in a row that find less and less."""
    sides = [A] * 4 + [B] + [A] * 2 + [B] * 3 + [A] * 3 + [B] + [A] + [B] * 2
    order = d.numbers(len(sides))
    for i, (f, side) in enumerate(zip(order, sides)):
        d.seeded(side, f, n_keys, seed)
        if i + 1 == len(sides) or sides[i + 1] != side:
            d.rt.drain()
    return order


def _two_threads(d: Deployment, n_keys: int, seed: int) -> list:
    """One A frame in a known place, then two senders at once: the order of
    their frames is whatever the engine made it, read back from the rows."""
    (first,) = d.numbers(1)
    d.seeded(A, first, n_keys, seed)
    d.rt.drain()
    # six frames a side: were every A frame to run before the first B, the
    # table (8 x the batch) still holds them all
    mine = {side: d.numbers(6) for side in (A, B)}
    threads = [threading.Thread(target=lambda s=side: [
        d.seeded(s, f, n_keys, seed) for f in mine[s]]) for side in (A, B)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    order = d.order_of(d.new_blocks(), [first] + mine[A])
    assert order[0] == first
    # each sender's frames keep their order; a B frame that ran right after
    # another may have found nothing and given no block (left out of the
    # replay, which then owes its rows to no later frame either)
    assert [f for f in order if f in mine[A]] == mine[A]
    answered = [f for f in order if f in mine[B]]
    assert answered == sorted(answered) and len(answered) >= 2
    return order


SCHEDULES = {"alternating": _alternating, "runs_of_a": _runs_of_a,
             "two_threads": _two_threads}


@pytest.mark.parametrize("size,schedule,key_share,seed", [
    (size, schedule, key_share, seed)
    for size in SIZES for schedule in SCHEDULES
    for key_share, seed in ((0.76, 7), (0.25, 8), (3.0, 9))])
def test_engine_equals_the_per_event_reference(size, schedule, key_share,
                                               seed):
    """`key_share` x the batch keys: 0.76 is pattern_ab's 1.31 events a key
    a frame; 0.25 piles several waiting A's on every key; 3.0 leaves most
    A's waiting over several B frames."""
    batch, pending = SIZES[size]
    d = _deployment(size, batch, pending)
    n_keys = max(2, int(batch * key_share))
    order = SCHEDULES[schedule](d, n_keys, seed)
    order += d.sweep(n_keys)
    stats = d.rt.statistics_report()
    assert set(stats["ingress_pipeline"]) == set(STREAMS), \
        "both pipelines engaged"
    assert not stats["overflow"]
    got, want = d.settle(order)
    assert len(want) > batch  # the case is not vacuous
    assert got == want  # rows, order, bits
    assert d.pattern_stats()["live"] == d.reference.waiting() == 0


def test_a_blocks_rows_come_in_upstreams_order(small):
    """By completing event, then by the partial match's arrival — not in
    pending-slot order: A = (x) (y) (x), B = (y) (x) gives (y), (x first),
    (x second)."""
    d = small
    fa, fb = d.numbers(2)
    d.send(A, fa, [90001, 90002, 90001])
    d.rt.drain()
    d.send(B, fb, [90002, 90001])
    got, want = d.settle([fa, fb])
    assert got == want
    assert [(r[4] % STRIDE, r[5] % STRIDE) for r in got] \
        == [(1, 0), (0, 1), (2, 1)]


def test_within_lets_go_per_arrival_against_the_arriving_events_stamp():
    """Every arriving quote lets go of the trades older than the bound
    against ITS OWN stamp, whatever the frame's later quotes or frames still
    undelivered are stamped: a trade whose quote lies within the bound on the
    pair's own stamps is matched though the frame's last stamp is beyond it."""
    d = _deployment("short_bound", 64, 512, within_s=30)  # 3 frame numbers
    f0, f1, f2, f3 = d.numbers(4)
    d.send(A, f0, [1, 2, 3, 5])  # stamped f0 * STRIDE + 0, 1, 2, 3
    d.rt.drain()
    d.send(A, f1, [1, 4])
    d.rt.drain()
    d.send(B, f2, [1])  # both waiting 1s, within the bound
    d.rt.drain()
    # f3 is 30,000 ticks after f0: its lane 1 is the bound to the tick for
    # f0's lane 1 (symbol 2: matched); its lane 3 is one tick beyond for
    # f0's lane 2 (symbol 3: let go by its own quote) and lets go of nothing
    # else; f0's lane 3 (symbol 5) has no quote and stays, within the bound
    # of every arrival
    d.send(B, f3, [7, 2, 8, 3])
    got, want = d.settle([f0, f1, f2, f3])
    assert got == want
    assert [(r[4], r[5]) for r in got] == [
        (f0 * STRIDE, f2 * STRIDE), (f1 * STRIDE, f2 * STRIDE),
        (f0 * STRIDE + 1, f3 * STRIDE + 1)]
    stats = d.pattern_stats()
    assert stats["expired"] == d.reference.let_go == 1
    assert stats["live"] == d.reference.waiting() == 2  # f0's 5, f1's 4
    (late,) = [d.numbers(6)[-1]]  # beyond the bound of them all
    d.send(B, late, [9])
    got, want = d.settle([late])
    assert got == want == []
    stats = d.pattern_stats()
    assert stats["expired"] == d.reference.let_go == 3 and stats["live"] == 0


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_engine_equals_the_reference_where_the_bound_bites(seed):
    """Seeded frames of both sides in a seeded order, frame numbers running
    back and forth over more than the bound (producers whose clocks run
    apart): every row, in order, and every trade let go."""
    d = _deployment("short_bound", 64, 512, within_s=30)
    rng = np.random.default_rng(seed)
    numbers = d.numbers(24)
    order = rng.permutation(numbers).tolist()  # stamps follow the number
    before = d.pattern_stats()["expired"], d.reference.let_go
    for f in order:
        d.seeded(int(rng.integers(0, 2)), f, 40, seed)
        d.rt.drain()
    order += d.sweep(40)
    got, want = d.settle(order)
    assert len(want) > d.batch
    assert got == want
    let_go = d.reference.let_go - before[1]
    assert let_go > d.batch
    assert d.pattern_stats()["expired"] - before[0] == let_go


# ------------------------------------------- keyed match == dense mask

P_TWIN, B_TWIN = 96, 32
TWIN_APP = """
@app:playback
define stream S1 (symbol string, price float, stamp long);
define stream S2 (symbol string, price float, stamp long);
@info(name = 'pattern') @capacity(pending='96')
from every t=S1 -> q=S2[{condition}] {within}
select t.symbol as symbol, t.price as tradePrice, q.price as quotePrice,
       t.stamp as tradeStamp, q.stamp as quoteStamp
insert into Out;
"""


def _twins(condition: str, within: str):
    """(runtime, the query matched by key, the same query on the dense mask:
    the runtime's own argument, no switch)."""
    rt = SiddhiManager().create_siddhi_app_runtime(
        TWIN_APP.format(condition=condition, within=within),
        batch_size=B_TWIN)
    keyed = rt.query_runtimes["pattern"]
    assert keyed.P == P_TWIN
    dense = PatternQueryRuntime(keyed.query, rt.ctx, rt.junctions, rt.tables,
                                rt.ctx.registry, "pattern_dense", keyed=False)
    return rt, keyed, dense


def _batch(rng, n_keys: int, n: int, ts0: int, burst=None) -> EventBatch:
    keys = rng.integers(1, n_keys + 1, B_TWIN)
    if burst is not None:
        keys[rng.random(B_TWIN) < 0.6] = burst
    valid = np.arange(B_TWIN) < n
    return EventBatch(
        ts=jnp.asarray(ts0 + np.arange(B_TWIN, dtype=np.int64)),
        cols={"symbol": jnp.asarray(keys.astype(np.int32)),
              "price": jnp.asarray(
                  (rng.integers(1, 4000, B_TWIN) * 0.25).astype(np.float32)),
              "stamp": jnp.asarray(ts0 + np.arange(B_TWIN, dtype=np.int64))},
        valid=jnp.asarray(valid), types=jnp.zeros((B_TWIN,), jnp.int8))


CASES = {
    # name: (condition, within, keys, burst key, ts step a batch, rounds)
    "uniform": (KEYED, "", 24, None, 100, 10),
    "few_keys": (KEYED, "", 3, None, 100, 10),
    "same_symbol_bursts": (KEYED, "", 24, 5, 100, 10),
    # 400 ticks a round, a bound of 1,000, batches of 32: a pair is 831
    # ticks apart at most or 1,169 at least, so the bound never falls inside
    # a batch, where the two arms differ (the test after this one)
    "within_bites": (KEYED, "within 1 sec", 40, None, 400, 12),
    "full_table": (KEYED, "", 400, None, 100, 12),
    "arrival_only_filter": ("q.symbol == t.symbol and q.price > 500.0", "",
                            12, None, 100, 10),
    "key_on_the_left": ("t.symbol == q.symbol and q.stamp > 0", "", 12,
                        None, 100, 8),
    "long_key": ("q.price > 100.0 and q.stamp == t.stamp", "", 12, None, 0,
                 8),
}


def _assert_equal(keyed, dense) -> None:
    for a, b in zip(jax.tree_util.tree_leaves(keyed),
                    jax.tree_util.tree_leaves(dense)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", CASES)
def test_the_keyed_match_equals_the_dense_mask_bit_for_bit(case):
    """The new step against the one that was there (`keyed=False` plans no
    key, so the position falls through to the `[B, P]` mask): state and
    output after every step. Where `within` bites the tables differ in
    WHEN an entry leaves (the mask's sweep runs at every step, against the
    step's clock; the keyed match lets go at a quote's arrival), so there
    the output of every step and, after a closing quote batch, the count of
    entries let go and of those alive."""
    condition, within, n_keys, burst, step, rounds = CASES[case]
    rt, keyed, dense = _twins(condition, within)
    try:
        assert keyed._key_plans.keys() == {1} and not dense._key_plans
        assert dense.stats_snapshot()["positions_by_key"] == []
        rng = np.random.default_rng(sum(case.encode()))
        delivered = 0
        sids = [sid for r in range(rounds) for sid in (
            ("S1", "S1", "S2") if r % 3 else ("S1", "S2", "S2"))] + ["S2"]
        for i, sid in enumerate(sids):
            n = int(rng.integers(B_TWIN // 2, B_TWIN + 1))
            ts0 = (i // 3) * step
            batch = _batch(rng, n_keys, n, ts0, burst)
            now = jnp.int64(ts0 + B_TWIN - 1)  # playback: the newest stamp
            keyed.state, out_k = keyed._steps[sid](keyed.state, batch, now)
            dense.state, out_d = dense._steps[sid](dense.state, batch, now)
            if within:  # the lanes past the rows hold what the tables do
                valid = np.asarray(out_k.valid)
                np.testing.assert_array_equal(valid, np.asarray(out_d.valid))
                _assert_equal(*(jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[valid], (o.ts, o.cols))
                    for o in (out_k, out_d)))
            else:
                _assert_equal((keyed.state, out_k), (dense.state, out_d))
            delivered += int(np.asarray(out_k.valid).sum())
        assert delivered > B_TWIN  # the case is not vacuous
        counted = [{k: int(v) for k, v in q.device_counters().items()
                    if not (within and k == "live_hwm")}  # WHEN, again
                   for q in (keyed, dense)]
        assert counted[0] == counted[1]
        if case == "within_bites":
            assert counted[0]["expired"] > B_TWIN
        if case == "full_table":
            assert counted[0]["dropped"] > 0
            assert counted[0]["live_hwm"] == P_TWIN
        else:
            assert counted[0]["dropped"] == 0
    finally:
        rt.shutdown()


def test_where_the_bound_falls_inside_a_batch_the_two_arms_differ():
    """The known difference (docs/PARITY.md): the dense mask sweeps a table
    once a step against the step's clock, which under playback is the
    batch's newest stamp, before it matches; the keyed match lets go per
    arrival, as upstream and the per-event reference do. A trade at 0 with a
    bound of 1,000 and quotes at 1,000 (its own) and 1,031: the keyed step
    gives the pair, the dense one has swept the trade."""
    rt, keyed, dense = _twins(KEYED, "within 1 sec")
    try:
        def batch(ts0: int, keys) -> EventBatch:
            stamps = ts0 + np.arange(B_TWIN, dtype=np.int64)
            return EventBatch(
                ts=jnp.asarray(stamps),
                cols={"symbol": jnp.asarray(np.resize(
                    np.asarray(keys, np.int32), B_TWIN)),
                    "price": jnp.ones((B_TWIN,), jnp.float32),
                    "stamp": jnp.asarray(stamps)},
                valid=jnp.asarray(np.arange(B_TWIN) < len(keys)),
                types=jnp.zeros((B_TWIN,), jnp.int8))

        rows = {}
        for name, q in (("keyed", keyed), ("dense", dense)):
            q.state, _ = q._steps["S1"](q.state, batch(0, [1]),
                                        jnp.int64(0))
            q.state, out = q._steps["S2"](
                q.state, batch(1000, [1] + [2] * 31), jnp.int64(1031))
            rows[name] = int(np.asarray(out.valid).sum())
            assert int(q.device_counters()["expired"]) == 1 - rows[name]
        assert rows == {"keyed": 1, "dense": 0}
        reference = EveryAThenB(1000)
        reference.arrive_a(1, 0, "a")
        assert reference.arrive_b(1, 1000, "b") == [("a", "b")]
    finally:
        rt.shutdown()


@pytest.mark.parametrize("condition", [
    "q.price > t.price",                          # no key conjunct
    "q.symbol == t.symbol and q.price > t.price",  # a conjunct reads t
    "q.symbol == t.symbol or q.price > 5.0",       # not a conjunct
    "q.price == t.price",                          # a float is no key
])
def test_other_conditions_stay_on_the_dense_mask(condition):
    rt, keyed, _ = _twins(condition, "")
    try:
        assert not keyed._key_plans
        assert keyed.stats_snapshot()["positions_by_key"] == []
    finally:
        rt.shutdown()


def _sizes_in(jaxpr) -> list:
    """Element counts of every intermediate of a jaxpr, sub-jaxprs
    included."""
    sizes = []
    for eqn in jaxpr.eqns:
        sizes += [int(np.prod(v.aval.shape)) for v in eqn.outvars
                  if hasattr(v.aval, "shape")]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes += _sizes_in(sub)
    return sizes


def test_the_keyed_step_has_no_intermediate_of_b_times_p_elements():
    """pattern_ab's query at its rehearsal sizes: the B step's largest
    intermediate is a few (B + P)-lane columns, never the [B, P] mask —
    which the dense arm of the same query does build."""
    batch, pending = SIZES["rehearsal"]
    d = _deployment("rehearsal", batch, pending)
    q = d.query
    dense = PatternQueryRuntime(q.query, d.rt.ctx, d.rt.junctions,
                                d.rt.tables, d.rt.ctx.registry, "dense",
                                keyed=False)
    empty = EventBatch.empty(d.rt.junctions[STREAMS[1]].definition, batch)
    state = jax.tree_util.tree_map(jnp.copy, q.state)

    def largest(runtime):
        step = runtime._make_step(STREAMS[1])
        return max(_sizes_in(jax.make_jaxpr(step)(
            state, empty, jnp.int64(0)).jaxpr))

    assert largest(dense) >= batch * pending
    assert largest(q) <= 16 * (batch + pending) < batch * pending


# ------------------------------------------------ capacity, stated and priced


def test_the_capacity_stated_in_the_app_is_the_tables(small):
    q = small.query
    assert q.P == SIZES["small"][1]
    assert all(t.valid.shape == (q.P,) for t in q.state.pending)
    assert small.pattern_stats()["pending_capacity"] == q.P


def test_an_app_that_says_nothing_gets_the_process_wide_default():
    from siddhi_tpu.core import dtypes
    rt = SiddhiManager().create_siddhi_app_runtime(
        "define stream S1 (k int); define stream S2 (k int);"
        "@info(name='p') from every a=S1 -> b=S2[b.k == a.k] "
        "select a.k as k insert into Out;", batch_size=16)
    try:
        assert rt.query_runtimes["p"].P \
            == dtypes.config.pattern_pending_capacity == 1024
    finally:
        rt.shutdown()


@pytest.mark.parametrize("stated", ["0", "-4", "many", "1.5", "2147483648"])
def test_a_bad_capacity_is_refused_at_build(stated):
    with pytest.raises(SiddhiAppCreationError, match="pending"):
        SiddhiManager().create_siddhi_app_runtime(
            "define stream S1 (k int); define stream S2 (k int);"
            f"@info(name='p') @capacity(pending='{stated}') "
            "from every a=S1 -> b=S2[b.k == a.k] "
            "select a.k as k insert into Out;", batch_size=16)


def test_a_dense_mask_beyond_the_devices_memory_is_refused_at_build():
    """pattern_ab's sizes with a condition no key can serve: the [B, P]
    mask alone is 137 GB. Refused with the reason at build, not at the
    first frame; the keyed condition at the same sizes is only built (its
    tables are 80 MB), never stepped here."""
    app = ("define stream S1 (k int, v float); define stream S2 (k int, v "
           "float); @info(name='p') @capacity(pending='1048576') "
           "from every a=S1 -> b=S2[{}] select a.k as k insert into Out;")
    with pytest.raises(SiddhiAppCreationError, match="dense"):
        SiddhiManager().create_siddhi_app_runtime(
            app.format("b.v > a.v"), batch_size=131072)
    rt = SiddhiManager().create_siddhi_app_runtime(
        app.format("b.k == a.k"), batch_size=131072)
    try:
        assert rt.query_runtimes["p"].stats_snapshot()[
            "positions_by_key"] == [1]
    finally:
        rt.shutdown()


def test_the_cost_model_prices_the_querys_own_capacity():
    from siddhi_tpu.analysis.cost import compute_cost

    def state_bytes(annotation: str) -> int:
        report = compute_cost(compiler.parse(
            "define stream S1 (k int); define stream S2 (k int);"
            f"@info(name='p') {annotation} "
            "from every a=S1 -> b=S2[b.k == a.k] "
            "select a.k as k insert into Out;"), batch_size=16)
        (element,) = [e for e in report.elements if e.element == "p"]
        return element.state_bytes

    small_table, default = state_bytes("@capacity(pending='64')"), \
        state_bytes("")
    # per entry: a.k, its frame's valid and ts, and the bookkeeping
    assert default - small_table == (1024 - 64) * (4 + 1 + 8 + 31)


# ------------------------------------------------------ spans and counters


def test_counters_read_as_deltas_and_the_high_water_starts_anew(small):
    d = small
    before = d.pattern_stats()
    order = _runs_of_a(d, 48, seed=21)
    after = d.pattern_stats()
    n_a = sum(d.sent[f][0] == A for f in order)
    assert after["steps"][STREAMS[0]] - before["steps"][STREAMS[0]] == n_a
    assert after["steps"][STREAMS[1]] - before["steps"][STREAMS[1]] \
        == len(order) - n_a
    # an A step's out block is one empty lane, a B step's the whole table
    assert after["out_lanes"] - before["out_lanes"] \
        == n_a + (len(order) - n_a) * d.query.P
    assert after["live_hwm"] >= 4 * d.batch  # the run of four A frames
    assert after["live"] <= after["live_hwm"] <= d.query.P
    assert after["pending_dropped"] == 0
    for cell in ("step_" + STREAMS[0], "step_" + STREAMS[1], "drop_sync"):
        assert cell in after["stage_ms"]
    # the drop counter is synced every 64th step only
    assert after["stage_ms"]["drop_sync"]["batches"] \
        == sum(after["steps"].values()) // 64
    # the high water starts anew at a statistics_report(), not at the
    # heartbeat's sweep of the same counters
    d.rt.collect_overflow()
    assert d.query.synced["live_hwm"] == after["live"]
    d.seeded(A, d.numbers(1)[0], 48, seed=21)
    d.rt.drain()
    d.rt.collect_overflow()
    d.rt.collect_overflow()
    assert d.pattern_stats()["live_hwm"] == after["live"] + d.batch
    order.append(d.frames - 1)
    got, want = d.settle(order + d.sweep(48))
    assert got == want


def test_pattern_step_spans_nest_in_the_feeders_dispatch(small, tmp_path):
    """Inside a profiler session every `siddhi.pattern.step` lies in the
    `siddhi.feeder.dispatch` of the batch it runs, and says its stream."""
    import glob
    import os

    from jax.profiler import ProfileData
    d = small
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # Python frames: large, unread
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        order = _alternating(d, 48, seed=22)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("siddhi.pattern.step",
                               "siddhi.feeder.dispatch"):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    steps, dispatches = (events["siddhi.pattern.step"],
                         events["siddhi.feeder.dispatch"])
    assert sorted(str(stats["stream"]) for _, _, stats in steps) \
        == sorted(STREAMS * (len(order) // 2))
    for a, z, _ in steps:
        assert any(da <= a and z <= dz for da, dz, _ in dispatches)
    got, want = d.settle(order + d.sweep(48))
    assert got == want


def test_the_patterns_programs_carry_their_own_names(small):
    """A profiler's `XLA Modules` line names a program `jit_<function>`: the
    pattern's are told from every other query's `jit_step`, per fed
    stream."""
    q = small.query
    for sid in STREAMS:
        empty = EventBatch.empty(small.rt.junctions[sid].definition,
                                 small.batch)
        lowered = q._steps[sid].lower(q.state, empty, jnp.int64(0))
        assert f"jit_pattern_step_{sid}" in lowered.as_text()[:400]
