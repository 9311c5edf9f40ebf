"""The stream-stream windowed join against its plain per-event reference
(tests/join_reference.py), through the normal path: SXF1 frames ->
`wire.deliver_frames` -> two `@Async` streams, each with its own
IngressPipeline -> the jitted join steps -> AsyncDecoder -> columnar
callback. The result of a join depends on how the engine serialised the two
streams' frames, so the order is read back from the delivered blocks (a
block's timestamps are its trigger events', and every event's timestamp is
`frame * STRIDE + row`) and the reference replays that order: rows, order
and bits must be equal.
"""

import threading
import time

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, compiler
from siddhi_tpu.core import dtypes
from siddhi_tpu.io import wire

from .join_reference import LEFT, RIGHT, WindowedJoin

STREAMS = ("cseEventStream", "quoteEventStream")
STRIDE = 10000  # event indexes per frame number; frames are smaller
APP = """
@app:name('Join{batch}x{window}')
@Async(buffer.size='{batch}', workers='2')
define stream cseEventStream   (symbol string, price float, volume long, timestamp long);
@Async(buffer.size='{batch}', workers='2')
define stream quoteEventStream (symbol string, price float, volume long, timestamp long);
@info(name = 'join')
from cseEventStream#window.length({window}) as t
join quoteEventStream#window.length({window}) as q
on t.symbol == q.symbol
select t.symbol as symbol, t.price as tradePrice, q.price as quotePrice,
       t.timestamp as tradeStamp, q.timestamp as quoteStamp
insert into joinedStream;
"""


class Deployment:
    """One runtime of the join and, beside it, the per-event reference fed
    the same frames in the order the engine ran them. Shared by the tests
    of one size (a runtime costs two compiles): each test sends its frames,
    then `settle`s: what came out since the last settle against what the
    reference gives for those frames."""

    def __init__(self, batch: int, window: int) -> None:
        text = APP.format(batch=batch, window=window)
        self.batch, self.window = batch, window
        self.rt = SiddhiManager().create_siddhi_app_runtime(
            text, batch_size=batch, async_callbacks=True)
        self.blocks: list = []
        self.hook = None  # a test's own look at each block, before the log
        self.rt.add_callback("joinedStream", self._on_block, columnar=True)
        self.rt.start()
        definitions = compiler.parse(text).stream_definitions
        self.plans = [wire.schema_plan(definitions[s]) for s in STREAMS]
        self.handlers = [self.rt.get_input_handler(s) for s in STREAMS]
        self.sent: dict = {}
        self.reference = WindowedJoin(window)
        self.frames = 0  # frame numbers handed out
        self.settled = 0  # blocks compared so far

    def _on_block(self, block) -> None:
        if self.hook is not None:
            self.hook(block)
        self.blocks.append(block)

    def numbers(self, n: int) -> list:
        """The next `n` frame numbers (taken before any thread starts)."""
        self.frames += n
        return list(range(self.frames - n, self.frames))

    def send(self, side: int, f: int, keys, prices=None) -> None:
        """Frame `f` on `side`: one event per key, stamped with its global
        index both as its timestamp and in its `timestamp` attribute."""
        keys = np.asarray(keys)
        n = keys.size
        assert n <= STRIDE
        if prices is None:
            prices = np.random.default_rng([11, f]).integers(1, 4000, n) * 0.25
        prices = np.asarray(prices, np.float32)
        index = f * STRIDE + np.arange(n, dtype=np.int64)
        cols = {"symbol": np.array([f"S{k:05d}" for k in keys.tolist()],
                                   dtype=object),
                "price": prices, "volume": np.ones(n, np.int64),
                "timestamp": index}
        self.sent[f] = (side, keys, prices)
        body = wire.encode_frames(self.plans[side], cols, n, ts=index)
        assert wire.deliver_frames(self.handlers[side], body) == n

    def seeded(self, side: int, f: int, n_keys: int) -> None:
        self.send(side, f, np.random.default_rng([7, f]).integers(
            0, n_keys, self.batch))

    def new_blocks(self) -> list:
        self.rt.drain()
        return self.blocks[self.settled:]

    def order_of(self, blocks) -> list:
        """The serialisation, from the blocks: each names its trigger
        frame."""
        frames = []
        for b in blocks:
            mine = np.unique(b.timestamps // STRIDE)
            assert mine.size == 1, "a block answers one trigger frame"
            frames.append(int(mine[0]))
        return frames

    def settle(self, order) -> tuple:
        """(rows delivered since the last settle, rows the per-event
        reference gives for the frames in `order`), each row (trigger's
        timestamp, symbol, tradePrice bits, quotePrice bits, tradeStamp,
        quoteStamp)."""
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
            payloads = [(f * STRIDE + i, bits[i]) for i in range(keys.size)]
            for (t_idx, t_bits), (q_idx, q_bits) in self.reference.frame(
                    side, keys.tolist(), payloads):
                trigger = t_idx if side == LEFT else q_idx
                key = int(keys[trigger - f * STRIDE])
                want.append((trigger, f"S{key:05d}", t_bits, q_bits,
                             t_idx, q_idx))
        return got, want, self.order_of(blocks)

    def overflow(self) -> dict:
        return dict(self.rt.statistics_report()["overflow"])


SIZES = {"batch_under_window": (64, 200), "batch_is_window": (128, 128),
         "batch_over_window": (256, 200),  # join_100k's regime
         # and its step: 4,096 x join_max_matches 16 = 65,536 candidate
         # lanes compacted to a pair_cap of 32,768 (batches over 2,048 do)
         "batch_compacts": (4096, 3000)}
_deployments: dict = {}


def _deployment(name: str, batch: int, window: int) -> Deployment:
    if name not in _deployments:
        _deployments[name] = Deployment(batch, window)
    return _deployments[name]


@pytest.fixture(scope="module", autouse=True)
def _shut_down():
    yield
    for d in _deployments.values():
        d.rt.shutdown()
    _deployments.clear()


@pytest.fixture
def small():
    """Batch 64 under a window of 200: the tests that need no size."""
    return _deployment("batch_under_window", *SIZES["batch_under_window"])


# -------------------------------------------------- engine == reference

FRAMES = 12


def _alternating(d: Deployment, n_keys: int) -> list:
    order = d.numbers(FRAMES)
    for i, f in enumerate(order):
        d.seeded(i % 2, f, n_keys)
        d.rt.drain()  # fixes the order across the two streams' feeders
    return order


def _runs_of_one_side(d: Deployment, n_keys: int) -> list:
    sides = [LEFT] * 3 + [RIGHT] * 4 + [LEFT] * 2 + [RIGHT] + [LEFT] * 2
    order = d.numbers(len(sides))
    for i, (f, side) in enumerate(zip(order, sides)):
        d.seeded(side, f, n_keys)
        # within a run frames keep their order; a drain where the side
        # changes fixes the order across streams
        if i + 1 == len(sides) or sides[i + 1] != side:
            d.rt.drain()
    return order


def _two_threads(d: Deployment, n_keys: int) -> list:
    """Both windows filled in a known order, then two senders at once: the
    order of their frames is whatever the engine made it."""
    first = d.numbers(2)
    for f, side in zip(first, (LEFT, RIGHT)):
        d.seeded(side, f, n_keys)
        d.rt.drain()
    mine = {side: d.numbers(FRAMES) for side in (LEFT, RIGHT)}
    threads = [threading.Thread(target=lambda s=side: [
        d.seeded(s, f, n_keys) for f in mine[s]]) for side in (LEFT, RIGHT)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    answered = d.order_of(d.new_blocks())
    concurrent = [f for f in answered if f not in first]
    assert sorted(concurrent) == sorted(mine[LEFT] + mine[RIGHT]), \
        "every concurrent frame is answered by exactly one block"
    for side in (LEFT, RIGHT):  # each sender's frames keep their order
        assert [f for f in concurrent if f in mine[side]] == mine[side]
    return first + concurrent


SCHEDULES = {"alternating": _alternating, "runs_of_one_side":
             _runs_of_one_side, "two_threads": _two_threads}


@pytest.mark.parametrize("size,schedule", [
    (size, schedule) for size in SIZES for schedule in SCHEDULES
    if size != "batch_compacts" or schedule == "alternating"])
def test_engine_equals_the_per_event_reference(size, schedule):
    batch, window = SIZES[size]
    d = _deployment(size, batch, window)
    n_keys = window  # about one match a probe, as join_100k
    order = SCHEDULES[schedule](d, n_keys)
    stats = d.rt.statistics_report()
    assert set(stats["ingress_pipeline"]) == set(STREAMS), \
        "both pipelines engaged"
    assert not stats["overflow"]
    got, want, answered = d.settle(order)
    assert len(want) > batch  # the case is not vacuous
    assert got == want  # rows, order, bits
    # blocks come in step order; a frame with no pair gives no block
    assert answered == [f for f in order
                        if any(r[0] // STRIDE == f for r in want)]


# ------------------------------------------------ the bound on matches


@pytest.mark.parametrize("extra", [0, 1], ids=["k_max_matches",
                                               "one_more_than_k_max"])
def test_matches_per_probe_at_the_bound(extra):
    """`join_max_matches` duplicates of a key in the opposite window: every
    pair comes out and nothing is dropped. One more: the walk keeps the 16
    newest, and the probe it cut short is counted in
    `join_pairs_dropped` — exactly the pair the reference has and the
    engine has not."""
    k_max = dtypes.config.join_max_matches
    d = _deployment("bounded", 64, 200)
    before = d.overflow()
    hot = 70000 + extra  # keys no other frame of this runtime carries
    fresh = 80000 + 1000 * extra
    right, left = d.numbers(2)
    d.send(RIGHT, right, [hot] * (k_max + extra) + list(
        range(fresh, fresh + 64 - k_max - extra)))
    d.rt.drain()
    d.send(LEFT, left, [hot] + list(range(fresh + 100, fresh + 163)))
    d.rt.drain()
    got, want, _ = d.settle([right, left])
    assert len(want) == k_max + extra  # the one probe of the hot key
    if not extra:
        assert got == want and d.overflow() == before
    else:
        assert got == want[1:]  # oldest first: the oldest match is lost
        counter = "query:join.join_pairs_dropped"
        assert d.overflow().get(counter, 0) - before.get(counter, 0) \
            == len(want) - len(got) == 1
        assert d.rt.statistics_report()["joins"]["join"]["pairs_dropped"] \
            == d.overflow()[counter]


# --------------------------------------------------------------- drain()


def test_drain_returns_after_a_slow_last_callback_has_returned(small):
    d = small
    done = []

    def slow(block):
        time.sleep(0.4)
        done.append(block.count)

    left, right = d.numbers(2)
    d.seeded(LEFT, left, 50)
    d.rt.drain()
    back0 = d.rt.statistics_report()["readback"]
    d.hook = slow
    try:
        d.seeded(RIGHT, right, 50)
        d.rt.drain()
        assert len(done) == 1, "drain() came back before the callback had"
    finally:
        d.hook = None
    back = d.rt.statistics_report()["readback"]
    assert back["submitted"] == back["delivered"] == back0["submitted"] + 1
    got, want, _ = d.settle([left, right])
    assert got == want


# ------------------------------------------- counters, spans and names


def test_joins_section_of_statistics_report(small):
    d = small
    before = d.rt.statistics_report()["joins"]["join"]
    order = _alternating(d, 200)
    after = d.rt.statistics_report()["joins"]["join"]
    k_max = dtypes.config.join_max_matches
    assert after["k_max"] == k_max
    for side in ("left", "right"):
        assert after["steps"][side] - before["steps"][side] == FRAMES // 2
        a, z = (x["stage_ms"]["step_" + side] for x in (before, after))
        assert z["batches"] - a["batches"] == FRAMES // 2
        assert z["total_ms"] > a["total_ms"]
    lanes = len(order) * 64 * k_max
    assert after["candidate_lanes"] - before["candidate_lanes"] == lanes
    # small blocks keep their full width: 64 lanes x k_max candidates
    assert after["out_lanes"] - before["out_lanes"] == lanes
    assert after["pairs_dropped"] == 0
    # the drop counter is synced every 64th step only
    assert after["stage_ms"]["drop_sync"]["batches"] \
        == sum(after["steps"].values()) // 64
    got, want, _ = d.settle(order)
    assert got == want


def test_join_step_spans_nest_in_the_feeders_dispatch(small, tmp_path):
    """Inside a profiler session every `siddhi.join.step` lies in the
    `siddhi.feeder.dispatch` of the batch it runs, and says its side."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    d = small
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # Python frames: large, unread
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        order = _alternating(d, 200)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("siddhi.join.step", "siddhi.feeder.dispatch"):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    steps, dispatches = (events["siddhi.join.step"],
                         events["siddhi.feeder.dispatch"])
    assert sorted(str(stats["side"]) for _, _, stats in steps) \
        == ["left"] * (FRAMES // 2) + ["right"] * (FRAMES // 2)
    for a, z, _ in steps:
        assert any(da <= a and z <= dz for da, dz, _ in dispatches)
    got, want, _ = d.settle(order)
    assert got == want


def test_the_joins_programs_carry_their_own_names(small):
    """A profiler's `XLA Modules` line names a program `jit_<function>`: the
    join's three are told from every other query's `jit_step`."""
    import jax.numpy as jnp

    from siddhi_tpu.core.event import EventBatch
    from siddhi_tpu.core.join_runtime import JoinQueryRuntime
    join = small.rt.query_runtimes["join"]
    assert isinstance(join, JoinQueryRuntime)
    empty = EventBatch.empty(small.rt.junctions[STREAMS[0]].definition, 64)
    for side, fn in (("left", join._step_left), ("right", join._step_right)):
        lowered = fn.lower(join.state, empty, jnp.int64(0), None)
        assert f"jit_join_probe_{side}" in lowered.as_text()[:400]


# ------------------------------------- the multimap's buckets and its hash


def test_multimap_buckets_leave_a_chain_to_the_probes_own_matches():
    """A walk spends `join_max_matches` steps on chain entries whether they
    match or not: join_100k lost pairs on the chip with 2x the ring's
    buckets and the FNV round's lumpy low bits (PERF.md, PR 26)."""
    import jax.numpy as jnp

    from siddhi_tpu.ops.groupby import hash_columns32
    from siddhi_tpu.ops.join import multimap_buckets
    assert multimap_buckets(131072) == 1 << 21
    assert multimap_buckets(200) == 4096
    # dense interned ids, as a string key's codes are
    h = np.asarray(hash_columns32([jnp.arange(100010, dtype=jnp.int32)]))
    assert np.unique(h).size == h.size  # bijective: equality is unchanged
    load = np.bincount(h & np.uint32((1 << 18) - 1), minlength=1 << 18)
    # Poisson(0.38) over 2^18 buckets: 5 ids in a bucket about ten times,
    # 6 about once, 7 never (the FNV round alone: 50, 14 and 2)
    assert load.max() <= 6
    assert np.count_nonzero(load >= 5) <= 30


# ------------------------------------------------- the packed multimap


class PerEventMultimap:
    """The multimap one event at a time, in plain dicts: a row takes the
    ring position of its arrival index, remembers the position its bucket's
    newest row had before it, and becomes that newest row. A probe starts at
    its bucket's newest position and follows those memories through
    whatever rows hold the positions NOW, while the ages (arrivals since
    the row's own) strictly grow and stay inside the window; it examines
    `k_max` rows whether they match or not, keeps the positions of those
    with its own hash, oldest first, and is truncated if one more row would
    have passed."""

    def __init__(self, ring: int, buckets: int, start: int) -> None:
        self.C, self.H, self.n = ring, buckets, start
        self.slot: dict = {}  # position -> (arrival index, hash, older)
        self.newest: dict = {}  # bucket -> position

    def append(self, hashes, live) -> None:
        for h, ok in zip(hashes.tolist(), live.tolist()):
            if ok:
                pos, b = self.n % self.C, h % self.H
                self.slot[pos] = (self.n, h, self.newest.get(b, -1))
                self.newest[b] = pos
                self.n += 1

    def probe(self, h: int, window_len: int, k_max: int):
        pos, age_before, found = self.newest.get(h % self.H, -1), 0, []
        for examined in range(k_max + 1):
            if pos < 0:
                break
            arrived, its_hash, older = self.slot[pos]
            if not age_before < self.n - arrived <= window_len:
                break
            if examined == k_max:
                return found[::-1], True
            found += [pos] * (its_hash == h)
            pos, age_before = older, self.n - arrived
        return found[::-1], False


@pytest.mark.parametrize("ring,length,buckets,k_max,start", [
    pytest.param(40, 40, 8, 4, 0, id="ring_wraps_three_times"),
    pytest.param(40, 25, 8, 4, 7, id="window_shorter_than_the_ring"),
    pytest.param(40, 40, 8, 4, 2**32 - 70, id="arrival_tags_cross_2^32"),
    pytest.param(40, 40, 1, 4, 0, id="one_bucket_deeper_than_k_max"),
    pytest.param(16, 16, 2, 16, 3, id="chains_diverted_every_batch"),
])
def test_packed_multimap_equals_a_per_event_dict_of_lists(
        ring, length, buckets, k_max, start):
    """`multimap_append` + `multimap_probe` over a ring that wraps: keys
    repeat inside a batch, a quarter of the lanes are invalid, buckets are
    so few that every chain runs into slots another bucket has overwritten,
    and (one bucket) deeper than `k_max`: candidates, their order and
    `truncated` as the per-event model gives them."""
    import jax.numpy as jnp

    from siddhi_tpu.ops.join import (multimap_append, multimap_init,
                                     multimap_probe)
    B = 16
    rng = np.random.default_rng([37, ring, buckets, start % 97])
    keys = rng.integers(0, 2**32, 12, dtype=np.uint64).astype(np.uint32)
    mm = multimap_init(ring, buckets)
    model = PerEventMultimap(ring, buckets, start)
    appended = start
    truncated_seen = matched = 0
    for step in range(11):
        hashes = keys[rng.integers(0, keys.size, B)]
        live = rng.random(B) > 0.25
        mm = multimap_append(mm, jnp.asarray(hashes), jnp.asarray(live),
                             jnp.int64(appended))
        model.append(hashes, live)
        appended += int(live.sum())
        probes = keys[rng.integers(0, keys.size, B)]
        valid = rng.random(B) > 0.2
        window_len = min(appended - start, length)
        pos, ok, truncated = multimap_probe(
            mm, jnp.asarray(probes), jnp.asarray(valid), jnp.int64(appended),
            jnp.int64(window_len), k_max)
        pos, ok = np.asarray(pos), np.asarray(ok)
        want = [model.probe(int(h), window_len, k_max) if v else ([], False)
                for h, v in zip(probes, valid)]
        assert [pos[i][ok[i]].tolist() for i in range(B)] \
            == [w[0] for w in want], step
        assert int(truncated) == sum(w[1] for w in want), step
        truncated_seen += int(truncated)
        matched += int(ok.sum())
    assert appended - start > 3 * ring  # the ring wrapped three times
    assert matched > B and (truncated_seen > 0) == (k_max < ring // buckets)


def _gathers_by_stage(lowered) -> dict:
    """{stage: [operand type of each `stablehlo.gather` under
    `siddhi.<stage>`]} of a lowered step program."""
    import re
    text = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, flags=re.M))

    def stage_of(ref: str, depth: int = 0):
        body = locs.get(ref, "")
        named = re.search(r"siddhi\.(\w+)", body)
        if named or depth == 4:
            return named and named.group(1)
        return next(filter(None, (stage_of(r, depth + 1) for r in
                                  re.findall(r"#loc\d+", body))), None)

    found: dict = {}
    ops = re.findall(r'"stablehlo\.gather"\(.*? : \((tensor<[^>]*>), '
                     r'.*? loc\((#loc\d+)\)', text)
    assert len(ops) == text.count('"stablehlo.gather"(')
    for operand, ref in ops:
        found.setdefault(stage_of(ref), []).append(operand)
    return found


@pytest.mark.parametrize("side", ["left", "right"])
def test_the_join_step_gathers_rows_not_words(small, side):
    """In place of an engagement counter (the packing is chosen at trace
    time): the chain walk reads one packed entry a step (`heads`, sixteen
    steps, the truncation's: 18 gathers where the three-array multimap took
    50), and both sides' pair frames cross as packed 32-bit rows — no
    `f32` and no 8-byte operand under `siddhi.frames` (`price` gathered as
    `f32[524288]` was 16 of join_100k's 104 ms step: PERF.md, PR 34)."""
    import re

    import jax.numpy as jnp

    from siddhi_tpu.core.event import EventBatch
    join = small.rt.query_runtimes["join"]
    fn = join._step_left if side == "left" else join._step_right
    stream = STREAMS[0 if side == "left" else 1]
    empty = EventBatch.empty(small.rt.junctions[stream].definition, 64)
    found = _gathers_by_stage(fn.lower(join.state, empty, jnp.int64(0), None))
    k_max = dtypes.config.join_max_matches
    assert 0 < len(found["probe"]) <= k_max + 2
    entry = "tensor<%dx3xui32>" % join.state[2].slots.shape[0]
    assert found["probe"].count(entry) == k_max + 1
    assert len(found["frames"]) == 2
    assert not [t for t in found["frames"]
                if re.search(r"x(f32|[iu]i?64|f64)>", t)]
    # the append gathers the hash by the bucket order, nothing else by it
    assert len(found["window"]) <= 4


def test_restore_converts_a_snapshot_the_three_array_multimap_wrote(
        monkeypatch):
    """A join query's snapshot holds its multimaps. One written before
    PR 37 pickles `MultimapState(heads, nexts, slot_hash, slot_seq)`; the
    class takes those four on the way in and packs the three per-slot
    arrays into its table, so `restore` leaves the state the snapshot's
    writer had and the next step finds the pairs it would have found."""
    import pickle
    from collections import namedtuple

    import jax

    from siddhi_tpu.ops import join as J
    app = ("@app:name('Upgrade')\n"
           "define stream L (k int, v int);\n"
           "define stream R (k int, v int);\n"
           "@info(name = 'join')\n"
           "from L#window.length(6) join R#window.length(6) on L.k == R.k "
           "select L.k as k, L.v as lv, R.v as rv insert into OutStream;")

    def deployment():
        rt = SiddhiManager().create_siddhi_app_runtime(app, batch_size=8)
        got: list = []
        rt.add_callback("OutStream",
                        lambda evs: got.extend(e.data for e in evs))
        rt.start()
        return rt, got

    def feed(rt, frames):
        for stream, rows in frames:
            rt.get_input_handler(stream).send_batch(rows)
            rt.flush()

    before = [("L", [(k % 4, k) for k in range(8)]),  # the rings wrap
              ("R", [(k % 3, 100 + k) for k in range(7)])]
    after = [("R", [(1, 200), (3, 201)]), ("L", [(0, 50), (2, 51)])]
    rt, got = deployment()
    feed(rt, before)
    snap = pickle.loads(rt.snapshot())
    written = snap["queries"]["join"]
    assert [type(s) for s in written[2:4]] == [J.MultimapState] * 2

    parent = namedtuple("MultimapState", "heads nexts slot_hash slot_seq")
    parent.__module__ = J.__name__

    def as_the_parent_wrote(mm):
        seq, key_hash, older = mm.slots.T
        return parent(mm.heads, older.view(np.int32), key_hash, seq)

    snap["queries"]["join"] = written[:2] + tuple(
        as_the_parent_wrote(mm) for mm in written[2:4]) + written[4:]
    with monkeypatch.context() as m:  # pickled by reference, as it was
        m.setattr(J, "MultimapState", parent)
        blob = pickle.dumps(snap, protocol=pickle.HIGHEST_PROTOCOL)
    assert blob != rt.snapshot()

    rt2, got2 = deployment()
    rt2.restore(blob)
    restored = rt2.query_runtimes["join"].state
    assert [type(s) for s in restored[2:4]] == [J.MultimapState] * 2
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(rt.query_runtimes["join"].state)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))
    del got[:]
    feed(rt, after)
    feed(rt2, after)
    # R(1), R(3) against L's last six, then L(0), L(2) against R's
    assert got2 == got == [
        (1, 5, 200), (3, 3, 201), (3, 7, 201),
        (0, 50, 103), (0, 50, 106), (2, 51, 105)]
    rt.shutdown()
    rt2.shutdown()
