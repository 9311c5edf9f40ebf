"""`#window.time` with distinctCount through the served path (SXF1 frames ->
`wire.deliver_frames` -> an `@Async` stream's IngressPipeline -> the jitted
step -> AsyncDecoder -> columnar callback) against the plain per-event
reference (tests/window_reference.py): expiry by upstream's FIFO rule on the
running-maximum clock whatever the stamps' order and wherever the app's
clock stands; a window capacity and expiry width the app states
(`@capacity(window=, expire=)`); the two loss counters; and a step whose
cost follows the batch, not the ring.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from siddhi_tpu import SiddhiManager, compiler
from siddhi_tpu.errors import SiddhiAppCreationError
from siddhi_tpu.io import wire
from siddhi_tpu.ops import windows as W

from .window_reference import DistinctTimeWindow, distinct_counts

BATCH, STRIDE, SYMBOLS = 64, 300, 20
APP = """
@app:name('Distinct')
{playback}
@Async(buffer.size='{batch}', workers='2')
define stream S (symbol string, price float, volume long, timestamp long);
@info(name = 'distinct')
{capacity}
from S#window.{window}
select timestamp, distinctCount(symbol) as d
insert into O;
"""


def app_text(window="time(1 sec)", capacity="@capacity(window='1024', "
             "expire='256')", batch=BATCH, playback="@app:playback"):
    return APP.format(window=window, capacity=capacity, batch=batch,
                      playback=playback)


class Deployment:
    def __init__(self, text: str, batch: int = BATCH) -> None:
        self.rt = SiddhiManager().create_siddhi_app_runtime(
            text, batch_size=batch, group_capacity=4096,
            async_callbacks=True)
        self.blocks: list = []
        self.rt.add_callback("O", self.blocks.append, columnar=True)
        self.rt.start()
        self.plan = wire.schema_plan(compiler.parse(text)
                                     .stream_definitions["S"])
        self.handler = self.rt.get_input_handler("S")
        self.symbols: list = []
        self.stamps: list = []

    def send(self, stamps, seed: int, symbols=None) -> None:
        """One frame with these stamps (and symbols: seeded without),
        through the wire decoder and the ingress pipeline; the frame is run
        before the next is sent, so the serialisation is the order of the
        calls."""
        stamps = np.asarray(stamps, np.int64)
        n = stamps.size
        if symbols is None:
            ids = np.random.default_rng([5, seed]).integers(0, SYMBOLS, n)
            symbols = [f"S{k:03d}" for k in ids.tolist()]
        symbols = list(symbols)
        cols = {"symbol": np.array(symbols, dtype=object),
                "price": np.ones(n, np.float32),
                "volume": np.ones(n, np.int64), "timestamp": stamps}
        body = wire.encode_frames(self.plan, cols, n, ts=stamps)
        assert wire.deliver_frames(self.handler, body) == n
        self.rt.drain()
        self.symbols += symbols
        self.stamps += stamps.tolist()

    def frame(self, f: int) -> None:
        # stamps 4 ticks apart: a 1 s window is four frames wide
        self.send(f * STRIDE + 4 * np.arange(BATCH), f)

    def rows(self) -> tuple:
        self.rt.drain()
        ts = np.concatenate([b.timestamps for b in self.blocks])
        assert not any(b.is_expired.any() for b in self.blocks)
        return ts.tolist(), np.concatenate(
            [b.column("d") for b in self.blocks]).tolist()

    def close(self) -> None:
        self.rt.shutdown()


@pytest.fixture
def deploy():
    made = []

    def make(*args, **kw):
        made.append(Deployment(*args, **kw))
        return made[-1]
    yield make
    for d in made:
        d.close()


# ------------------------------------- the three experiments of ISSUE 32


@pytest.mark.parametrize("order, ahead", [
    (list(range(9)), 0),                       # stamps in order
    ([0, 1, 3, 2, 4, 6, 5, 7, 8], 0),          # producers' stamps interleaved
    (list(range(9)), 1500),                    # the app clock runs ahead
    ([0, 2, 1, 3, 6, 5, 4, 7, 8], 1500),       # both
], ids=["ordered", "interleaved", "clock_ahead", "both"])
def test_served_time_window_equals_the_per_event_reference(deploy, order,
                                                           ahead):
    d = deploy(app_text())
    for f in order:
        if ahead:
            # as on the served path with frames outstanding: frame k+1..k+3
            # are decoded, and their stamps observed, before frame k runs
            d.rt.ctx.timestamp_generator.observe_event_time(
                f * STRIDE + 4 * BATCH + ahead)
        d.frame(f)
    ts, got = d.rows()
    assert ts == d.stamps  # one row per event, in the order served
    want = distinct_counts(d.symbols, d.stamps, 1000)
    assert sum(g != w for g, w in zip(got, want)) == 0
    report = d.rt.statistics_report()
    assert not report["overflow"]
    assert report["windows"]["distinct"]["expired"] > BATCH  # it did expire


def test_a_stale_arrival_leaves_at_the_next_arrival_not_before(deploy):
    d = deploy(app_text())
    d.send([5000, 5001, 100, 5002, 5003], 1)  # 100 is older than the window
    d.send([5004, 6500, 6501], 2)
    ts, got = d.rows()
    assert got == distinct_counts(d.symbols, d.stamps, 1000)


@pytest.mark.parametrize("frames", [
    # X's one row leaves at the arrival of the next X: 1 -> 0 -> 1
    [([0, 1], "XY"), ([1500, 1501], "XZ")],
    # and again inside the same batch, with rows that came in it: the pair
    # count is read at 0 and at 1 twice over (`pair_post == 0`, `== 1`)
    [([0], "X"), ([1200, 1300, 2300, 2301, 3400], "XYXXX")],
    # a second X keeps the pair alive while the first leaves: 2 -> 1 -> 2
    [([0, 600], "XX"), ([1100, 1700], "XX")],
], ids=["leaves_and_returns", "twice_in_one_batch", "a_duplicate_stays"])
def test_a_symbol_that_leaves_and_returns_inside_one_batch(deploy, frames):
    d = deploy(app_text())
    for f, (stamps, symbols) in enumerate(frames):
        d.send(stamps, f, symbols=symbols)
    _, got = d.rows()
    assert got == distinct_counts(d.symbols, d.stamps, 1000)


class BudgetedWindow(DistinctTimeWindow):
    """The per-event walk with the step's expiry width: of the rows that are
    due, no more than `expire` leave in one step; the rest wait for the
    next."""

    def __init__(self, width: int, expire: int) -> None:
        super().__init__(width)
        self.budget = self.left = expire

    def expire(self) -> int:
        went = 0
        while self.left and self.due():
            self.pop()
            self.left -= 1
            went += 1
        return went

    def step(self, symbols, stamps) -> list:
        self.left = self.budget
        return [self.arrive(s, int(t)) for s, t in zip(symbols, stamps)]


@pytest.mark.filterwarnings("ignore:.*window_expiry_deferred")
def test_the_merges_corners_equal_the_per_event_reference(deploy):
    """What a merge of clocks and deadlines could break where a search did
    not: an expiry width that is neither the batch's nor a power of two (the
    deadlines' tail is a sentinel in most steps), frames out of stamp order
    by one to three, a batch whose every deadline ties a clock (a tie
    leaves first), and a jump of the clock that leaves rows behind for the
    next steps (`window_expiry_deferred`)."""
    expire, half = 48, BATCH // 2
    d = deploy(app_text(capacity=f"@capacity(window='1024', "
                                 f"expire='{expire}')"))
    ref, want = BudgetedWindow(1000, expire), []

    def send(stamps, seed):
        before = len(d.stamps)
        d.send(stamps, seed)
        want.extend(ref.step(d.symbols[before:], d.stamps[before:]))

    for f in [0, 3, 1, 2, 4, 7, 5, 6, 8]:  # a window is some three wide
        send(f * STRIDE + 8 * np.arange(half), f)
    w = d.rt.query_runtimes["distinct"].window
    assert (w.E, w.chunk_width) == (expire, BATCH + expire)

    def expired():
        return d.rt.statistics_report()["windows"]["distinct"]["expired"]
    assert expired() == len(d.stamps) - len(ref.fifo) > 0
    # every deadline at the head of the FIFO is the stamp of an arrival
    heads = sorted(t for _, t in list(ref.fifo)[:half])
    assert heads[0] + 1000 > ref.clock
    before = expired()
    send(np.asarray(heads) + 1000, 20)
    assert expired() - before >= half  # each tie let its row go
    assert expired() == len(d.stamps) - len(ref.fifo)
    # the clock jumps: every row is due, 48 may go a step
    live = len(ref.fifo)
    assert live > 2 * expire
    for f in (40, 41, 42, 43):
        send(f * STRIDE + 4 * np.arange(BATCH), f)
    ts, got = d.rows()
    assert ts == d.stamps
    assert sum(g != w_ for g, w_ in zip(got, want)) == 0
    stats = d.rt.statistics_report()
    assert stats["windows"]["distinct"]["expiry_deferred"] > expire
    assert expired() == len(d.stamps) - len(ref.fifo)
    # and the plain walk, which knows no width, says they did leave late
    assert got != distinct_counts(d.symbols, d.stamps, 1000)


def test_a_head_that_is_not_due_holds_the_rows_behind_it(deploy):
    d = deploy(app_text())
    # FIFO: 3000, then 1000 and 1001 behind it. At clock 2500 the two are
    # past their deadline, but the head (due at 4000) is not: nothing goes
    d.send([3000, 1000, 1001, 3400], 1)
    d.send([3500], 2)
    d.send([4000, 4001], 3)  # now the head goes, and the two with it
    _, got = d.rows()
    want = distinct_counts(d.symbols, d.stamps, 1000)
    assert got == want
    assert d.rt.statistics_report()["windows"]["distinct"]["expired"] == 3


def test_a_timer_batch_moves_the_playback_clock(deploy):
    d = deploy(app_text())
    d.frame(0)
    ref = DistinctTimeWindow(1000)
    for s, t in zip(d.symbols, d.stamps):
        ref.arrive(s, t)
    d.rt.heartbeat(now=1100)
    left = ref.timer(1100)
    assert 0 < left < BATCH
    d.frame(5)
    _, got = d.rows()
    want = [ref.arrive(s, t) for s, t in
            zip(d.symbols[BATCH:], d.stamps[BATCH:])]
    assert got[BATCH:] == want
    assert d.rt.statistics_report()["windows"]["distinct"]["expired"] \
        == len(d.stamps) - len(ref.fifo)


def test_more_rows_than_the_ring_pass_through_it(deploy):
    d = deploy(app_text(capacity="@capacity(window='128', expire='128')"))
    for f in range(12):  # 768 rows through a 128-row ring, 4 frames live
        d.send(f * STRIDE + 8 * np.arange(BATCH // 2), f)
    _, got = d.rows()
    assert got == distinct_counts(d.symbols, d.stamps, 1000)
    stats = d.rt.statistics_report()
    assert stats["windows"]["distinct"]["capacity"] == 128
    assert stats["windows"]["distinct"]["appended"] == 12 * BATCH // 2
    assert not stats["overflow"]


# --------------------------------------------- @capacity(window=, expire=)


@pytest.mark.parametrize("capacity, window, words", [
    ("@capacity(window='lots')", "time(1 sec)", "whole number"),
    ("@capacity(window='0')", "time(1 sec)", "whole number"),
    ("@capacity(expire='-3')", "time(1 sec)", "whole number"),
    ("@capacity(window='32')", "time(1 sec)", "at least one batch"),
    ("@capacity(window='128', expire='256')", "time(1 sec)",
     "cannot leave it in one step"),
    ("@capacity(window='4096')", "length(100)", "holds its count"),
    ("@capacity(window='4096')", "timeLength(1 sec, 100)", "holds its count"),
    ("@capacity(window='4096')", "lengthBatch(100)", "has none"),
    ("@capacity(window='1073741824')", "time(1 sec)", "more than the device"),
])
def test_capacity_is_validated_at_build(capacity, window, words):
    with pytest.raises(SiddhiAppCreationError, match=words):
        SiddhiManager().create_siddhi_app_runtime(
            app_text(window=window, capacity=capacity), batch_size=BATCH)


def test_stated_capacity_sizes_the_ring_and_defaults_stand_without(deploy):
    stated = deploy(app_text(capacity="@capacity(window='2048', "
                             "expire='512')"))
    w = stated.rt.query_runtimes["distinct"].window
    assert (w.C, w.E, w.chunk_width) == (2048, 512, BATCH + 512)
    assert stated.rt.query_runtimes["distinct"].state[0].ring.shape[1] == 2048
    plain = deploy(app_text(capacity=""))
    w = plain.rt.query_runtimes["distinct"].window
    assert (w.C, w.E) == (65536, 1024)
    length = deploy(app_text(window="length(100)",
                             capacity="@capacity(expire='32')"))
    w = length.rt.query_runtimes["distinct"].window
    assert (w.C, w.E) == (100, 32)


def test_cost_model_prices_the_stated_capacity():
    from siddhi_tpu.analysis.cost import compute_cost

    def ring_bytes(capacity):
        report = compute_cost(compiler.parse(app_text(capacity=capacity)),
                              batch_size=BATCH, group_capacity=4096)
        return report.state_bytes
    small = ring_bytes("@capacity(window='1024')")
    large = ring_bytes("@capacity(window='1048576')")
    # 8 words a row: symbol 1, price 1, volume 2, timestamp 2, the stamp 2
    assert large - small == (1048576 - 1024) * 8 * 4


# ----------------------------------------------------- the loss counters


def test_ring_overflow_is_counted_and_reported(deploy):
    d = deploy(app_text(window="time(100 sec)",
                        capacity="@capacity(window='128', expire='64')"))
    with pytest.warns(UserWarning, match="window_ring_overflow"):
        for f in range(4):  # 256 live rows into 128
            d.frame(f)
        stats = d.rt.statistics_report()
    assert stats["overflow"]["query:distinct.window_ring_overflow"] == 128
    assert stats["windows"]["distinct"]["ring_overflow"] == 128
    assert stats["windows"]["distinct"]["live_hwm"] == 256


def test_deferred_expiry_is_counted_and_reported(deploy):
    d = deploy(app_text(capacity="@capacity(window='1024', expire='16')"))
    for f in range(3):
        d.frame(f)
    d.frame(20)  # the clock jumps: 192 rows are due, 16 may go a step
    d.frame(21)
    with pytest.warns(UserWarning, match="window_expiry_deferred"):
        stats = d.rt.statistics_report()
    deferred = stats["windows"]["distinct"]["expiry_deferred"]
    assert deferred == stats["overflow"][
        "query:distinct.window_expiry_deferred"]
    # frame 20 let 16 of the 192 go; frame 21 found the rest still due
    assert deferred >= 16
    assert stats["windows"]["distinct"]["expired"] == 32


def test_the_windows_account_and_its_high_water(deploy):
    d = deploy(app_text())
    for f in range(6):
        d.frame(f)
    first = d.rt.statistics_report()["windows"]["distinct"]
    assert set(first) == {"capacity", "expire_width", "steps", "out_lanes",
                          "live", "live_hwm", "appended", "expired",
                          "ring_overflow", "expiry_deferred",
                          "selector_lanes", "stage_ms"}
    assert first["steps"] == 6
    assert first["out_lanes"] == 6 * (BATCH + 256)
    # expire 256 > the batch: the selector ran in rounds of one batch over
    # what arrived and left, at least one a step
    assert first["selector_lanes"] % BATCH == 0
    assert 6 * BATCH <= first["selector_lanes"] < first["out_lanes"]
    assert first["appended"] - first["expired"] == first["live"]
    assert first["live_hwm"] >= first["live"] > 0
    assert "drop_sync" in first["stage_ms"]
    d.rt.heartbeat(now=10 ** 6)  # everything leaves
    second = d.rt.statistics_report()["windows"]["distinct"]
    # the high water started anew at the first report, at what was live
    assert second["live_hwm"] == first["live"] and second["live"] == 0


# -------------------------------------- the step costs the batch, not the ring


@pytest.mark.parametrize("C, B", [(8, 8), (9, 8), (12, 8), (16, 8), (40, 8)])
def test_packed_helpers_equal_a_row_by_row_ring(C, B):
    rng = np.random.default_rng(C)
    for _ in range(40):
        ring = rng.integers(0, 2 ** 32, (3, C), dtype=np.uint32)
        comp = rng.integers(0, 2 ** 32, (3, B), dtype=np.uint32)
        a0, n = int(rng.integers(0, 1000)), int(rng.integers(0, B + 1))
        want = ring.copy()
        for p in range(n):
            want[:, (a0 + p) % C] = comp[:, p]
        got = W._append_packed(jnp.asarray(ring), jnp.asarray(comp),
                               jnp.int64(a0), jnp.int32(n))
        assert np.array_equal(np.asarray(got), want)
        E = int(rng.integers(1, C + 1))
        base = int(rng.integers(max(0, a0 - C), a0 + 1))
        rows = np.asarray(W._fetch_rel_packed(
            jnp.asarray(ring), jnp.asarray(comp), jnp.int64(base),
            jnp.int64(a0), E))
        for i in range(E):
            o = base + i
            if o < a0:
                assert np.array_equal(rows[:, i], ring[:, o % C])
            elif o - a0 < B:
                assert np.array_equal(rows[:, i], comp[:, o - a0])


def _compiled_step_memory(window: int, place=lambda shape: shape):
    """XLA's memory analysis of the served query's step at a ring of
    `window` rows; `place` may give every argument's shape a sharding."""
    from siddhi_tpu.core.event import EventBatch
    rt = SiddhiManager().create_siddhi_app_runtime(
        app_text(capacity=f"@capacity(window='{window}', expire='256')"),
        batch_size=BATCH, group_capacity=4096)
    qr = rt.query_runtimes["distinct"]
    batch = EventBatch.empty(qr.input_junction.definition, BATCH)
    shapes = jax.tree_util.tree_map(
        lambda x: place(jax.ShapeDtypeStruct(jnp.shape(x),
                                             jnp.result_type(x))),
        (qr.state, batch, jnp.int64(0)))
    memory = qr._step.lower(*shapes, {}).compile().memory_analysis()
    rt.shutdown()
    return memory


def test_the_steps_temporaries_do_not_grow_with_the_window():
    """XLA's own account of the compiled step at a 2^14-row and a 2^20-row
    ring (8 words a row: 0.5 MB and 32 MB): arguments and outputs follow
    the ring, which is donated and updated in place; temporaries must not."""
    small, large = _compiled_step_memory(2 ** 14), \
        _compiled_step_memory(2 ** 20)
    assert large.argument_size_in_bytes - small.argument_size_in_bytes \
        >= (2 ** 20 - 2 ** 14) * 32
    assert large.alias_size_in_bytes >= 2 ** 20 * 32  # the ring, in place
    assert abs(large.temp_size_in_bytes - small.temp_size_in_bytes) \
        < 2 * 2 ** 20, (small, large)


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) TPU v5e chip, for the TPU's own compiler.
    Inside a fixture, never at import: only the worker that runs this file
    loads the TPU's library (the on-chip-measurement guide, section 2)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.slow  # not tier-1: the TPU's compiler takes every core for 10 s
def test_compiled_for_the_tpu_the_step_does_not_copy_the_ring(one_chip):
    """The same, by the compiler of the chip the deployment runs on: the
    CPU backend's buffer assignment is not the TPU's. By hand:
    `pytest tests/test_time_window_served.py -m slow`."""
    def place(shape):
        return jax.ShapeDtypeStruct(shape.shape, shape.dtype,
                                    sharding=one_chip)
    small, large = _compiled_step_memory(2 ** 14, place), \
        _compiled_step_memory(2 ** 20, place)
    assert large.alias_size_in_bytes >= 2 ** 20 * 32
    assert abs(large.temp_size_in_bytes - small.temp_size_in_bytes) \
        < 2 * 2 ** 20, (small, large)
