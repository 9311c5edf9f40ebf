"""The keyed step of a value partition (core/keyed_partition.py,
ops/keyed_window.py, ops/slot_table.py) against its plain per-event
reference (tests/partition_reference.py), through the normal path: SXF1
frames -> `wire.deliver_frames` -> an `@Async` stream -> the partition's
receiver -> ONE jitted step a batch -> AsyncDecoder -> columnar callback.
Rows, their order and their bits must be the reference's.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, compiler
from siddhi_tpu.core import keyed_partition
from siddhi_tpu.io import wire

from .partition_reference import AGGREGATES, keyed_window_aggregates

BATCH = 64
APP = """
@app:name('Keyed{length}{agg}{capacity}')
@Async(buffer.size='{batch}', workers='2')
define stream TempStream (deviceID {key_type}, roomNo int, temp double, timestamp long);
@capacity(keys = '{capacity}')
partition with (deviceID of TempStream)
begin
    @info(name = 'deviceMax')
    from TempStream{filter}#window.length({length})
    select timestamp, roomNo, deviceID, {agg}(temp) as value
    insert into DeviceTempStream;
end;
"""
INT64_MAX = np.iinfo(np.int64).max


def app_text(length=10, agg="max", capacity=64, key_type="long", filter="",
             batch=BATCH) -> str:
    return APP.format(length=length, agg=agg, capacity=capacity,
                      key_type=key_type, filter=filter, batch=batch)


class Deployment:
    """One runtime of the guide's partition example; `send` posts a frame
    of events stamped with their global index, `rows` is what came out."""

    def __init__(self, text: str, batch: int = BATCH) -> None:
        self.rt = SiddhiManager().create_siddhi_app_runtime(
            text, batch_size=batch, async_callbacks=True)
        self.blocks: list = []
        self.rt.add_callback("DeviceTempStream", self.blocks.append,
                             columnar=True)
        self.rt.start()
        definition = compiler.parse(text).stream_definitions["TempStream"]
        self.plan = wire.schema_plan(definition)
        self.handler = self.rt.get_input_handler("TempStream")
        (self.partition,) = self.rt.partitions.values()
        self.sent = 0
        self.string_key = "deviceID string" in text

    def send(self, keys, temps) -> None:
        n = len(keys)
        index = self.sent + np.arange(n, dtype=np.int64)
        cols = {"deviceID": np.array(keys, dtype=object) if self.string_key
                else np.asarray(keys, np.int64),
                "roomNo": (index % 7).astype(np.int32),
                "temp": np.asarray(temps, np.float32),
                "timestamp": index}
        body = wire.encode_frames(self.plan, cols, n, ts=index)
        assert wire.deliver_frames(self.handler, body) == n
        self.sent += n

    def rows(self) -> list:
        """(stamp, roomNo, deviceID, value bits) per delivered row."""
        self.rt.drain()
        out = []
        for b in self.blocks:
            assert not b.is_expired.any()
            ids = b.strings("deviceID") if self.string_key \
                else b.column("deviceID").tolist()
            value = b.column("value")
            bits = value.astype(np.float32).view(np.int32).tolist() \
                if value.dtype.kind == "f" else value.tolist()
            assert b.column("timestamp").tolist() == b.timestamps.tolist()
            out.extend(zip(b.timestamps.tolist(),
                           b.column("roomNo").tolist(), ids, bits))
        return out

    def report(self) -> dict:
        return self.rt.statistics_report()


def expected(keys, temps, length, agg, capacity=None, passes=None):
    """The reference's rows for events 0..n-1, in the form of `rows`."""
    temps32 = np.asarray(temps, np.float32)
    idx = [i for i in range(len(keys)) if passes is None or passes[i]]
    values, away = keyed_window_aggregates(
        [keys[i] for i in idx], [float(temps32[i]) for i in idx], length,
        agg, capacity)
    want = []
    for i, v in zip(idx, values):
        if v is None:
            continue
        bits = int(np.float32(v).view(np.int32)) if agg != "count" else v
        want.append((i, i % 7, keys[i], bits))
    return want, away


def traffic(rng, frames=6, batch=BATCH):
    """Seeded frames of the cases the step has to get right: keys new and
    recurring across frames, negative keys and the old pad sentinel, one
    key with more than L and more than 2L events in a frame, and a frame
    that is one key. Temperatures on a grid of 1/64 (exact in float32, so
    sums are exact whatever their order)."""
    pool = np.concatenate([
        rng.integers(-2**62, 2**62, 9), [INT64_MAX, INT64_MAX - 1, -1, 0]])
    out = []
    for f in range(frames):
        keys = rng.choice(pool, batch)
        if f == 1:
            keys[5:30] = pool[0]  # 25 events of one key: > 2L for L = 10
        if f == 2:
            keys[10:22] = INT64_MAX  # > L
        if f == 3:
            keys[:] = pool[1]  # every lane one key
        out.append((keys.tolist(),
                    (rng.integers(640, 2560, batch) / 64.0).tolist()))
    return out


_deployments: dict = {}


def deployment(**kw) -> Deployment:
    key = tuple(sorted(kw.items()))
    if key not in _deployments:
        _deployments[key] = Deployment(app_text(**kw), kw.get("batch", BATCH))
    return _deployments[key]


@pytest.fixture(scope="module", autouse=True)
def _shut_down():
    yield
    for d in _deployments.values():
        d.rt.shutdown()
    _deployments.clear()


# ------------------------------------------------ the step == the reference


@pytest.mark.parametrize("agg", sorted(AGGREGATES))
@pytest.mark.parametrize("length", (1, 2, 10))
def test_rows_order_and_bits_are_the_references(length, agg):
    d = Deployment(app_text(length=length, agg=agg))
    try:
        assert d.partition.keyed is not None, d.partition.engine_reason
        keys, temps = [], []
        for ks, ts in traffic(np.random.default_rng([36, length])):
            d.send(ks, ts)
            keys += ks
            temps += ts
        want, away = expected(keys, temps, length, agg)
        assert away == 0
        assert d.rows() == want
        account = d.report()["partitions"][d.partition.name]
        assert account["keys_dropped"] == 0
        assert account["keys"] == len(set(keys))
        assert account["steps"] == 6 and account["out_lanes"] == 6 * BATCH
        assert "partition_keys_dropped" not in str(d.report()["overflow"])
    finally:
        d.rt.shutdown()


@pytest.mark.parametrize("length", (1, 2, 10))
def test_invalid_lanes_take_no_place_in_a_window(length):
    """A filter before the window: an event it drops is in no window and
    gives no row, also where it stands between two events of its key."""
    d = Deployment(app_text(length=length, filter="[temp >= 20.0]"))
    try:
        keys, temps = [], []
        for ks, ts in traffic(np.random.default_rng([37, length])):
            d.send(ks, ts)
            keys += ks
            temps += ts
        passes = [t >= 20.0 for t in temps]
        assert 0.2 < np.mean(passes) < 0.9
        want, _ = expected(keys, temps, length, "max", passes=passes)
        assert d.rows() == want
    finally:
        d.rt.shutdown()


@pytest.mark.parametrize("key_type,pool", [
    ("string", [f"dev-{i}" for i in range(11)]),
    ("int", [-7, -1, 0, 1, 2, 2**31 - 1, -2**31, 40, 41, 42]),
])
@pytest.mark.parametrize("length", (2, 10))
def test_string_and_int_keys_take_the_same_step(length, key_type, pool):
    d = Deployment(app_text(length=length, key_type=key_type, agg="sum"))
    try:
        assert d.partition.keyed is not None
        rng = np.random.default_rng([38, length])
        keys, temps = [], []
        for _ in range(4):
            ks = [pool[i] for i in rng.integers(0, len(pool), BATCH)]
            ts = (rng.integers(640, 2560, BATCH) / 64.0).tolist()
            d.send(ks, ts)
            keys += ks
            temps += ts
        want, _ = expected(keys, temps, length, "sum")
        assert d.rows() == want
    finally:
        d.rt.shutdown()


def test_a_bool_key_is_two_keys():
    rt = SiddhiManager().create_siddhi_app_runtime("""
        define stream S (on bool, x long);
        partition with (on of S) begin
            @info(name = 'q') from S#window.length(2)
            select on, sum(x) as total insert into Out;
        end;""", batch_size=8)
    got = []
    rt.add_callback("Out", lambda evs: got.extend(e.data for e in evs))
    rt.start()
    (pr,) = rt.partitions.values()
    assert pr.keyed is not None
    h = rt.get_input_handler("S")
    for row in [(True, 1), (False, 10), (True, 2), (True, 4), (False, 20)]:
        h.send(row)
    rt.flush()
    rt.shutdown()
    assert got == [(True, 1), (False, 10), (True, 3), (True, 6), (False, 30)]


# ------------------------------------------------------------ a full table


def test_keys_beyond_the_stated_capacity_are_counted_and_leave_no_row():
    """@capacity(keys='8') and 20 devices: the first eight to arrive hold
    the slots, every event of the others is counted in `keys_dropped` and
    gives no row, and the eight's rows are intact. The warning names the
    annotation."""
    d = Deployment(app_text(capacity=8))
    try:
        rng = np.random.default_rng(39)
        pool = rng.integers(-2**62, 2**62, 20)
        keys, temps = [], []
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for _ in range(5):
                ks = rng.choice(pool, BATCH).tolist()
                ts = (rng.integers(640, 2560, BATCH) / 64.0).tolist()
                d.send(ks, ts)
                keys += ks
                temps += ts
            want, away = expected(keys, temps, 10, "max", capacity=8)
            assert away > 0 and len(want) + away == len(keys)
            assert d.rows() == want
            report = d.report()
        account = report["partitions"][d.partition.name]
        assert account["keys"] == 8 == account["capacity"]
        assert account["keys_dropped"] == away
        assert report["overflow"][
            "query:deviceMax.partition_keys_dropped"] == away
        assert any("@capacity(keys=" in str(w.message) for w in caught)
    finally:
        d.rt.shutdown()


def test_the_drop_counter_is_synced_every_64th_step_not_each():
    d = Deployment(app_text(capacity=4, length=2, batch=16), batch=16)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for f in range(130):
                d.send([f % 9] * 16, [20.0] * 16)
            d.rt.drain()
            cells = d.partition.keyed.cells.snapshot()
            assert cells["step"]["batches"] == 130
            assert cells["drop_sync"]["batches"] == 1  # then it has warned
            assert d.partition.keyed.synced["keys_dropped"] > 0
    finally:
        d.rt.shutdown()


@pytest.mark.parametrize("capacity", ("0", "x", str(2**31)))
def test_a_capacity_that_is_no_count_is_refused(capacity):
    from siddhi_tpu.errors import SiddhiAppCreationError
    with pytest.raises(SiddhiAppCreationError, match="capacity"):
        SiddhiManager().create_siddhi_app_runtime(
            app_text(capacity=capacity), batch_size=BATCH)


def test_a_ring_beyond_2_30_rows_is_refused():
    from siddhi_tpu.errors import SiddhiAppCreationError
    with pytest.raises(SiddhiAppCreationError, match="rows of ring"):
        SiddhiManager().create_siddhi_app_runtime(
            app_text(capacity=2**28, length=10), batch_size=BATCH)


def test_an_app_that_states_nothing_gets_the_runtimes_partition_capacity():
    text = app_text().replace("@capacity(keys = '64')\n", "")
    rt = SiddhiManager().create_siddhi_app_runtime(
        text, batch_size=BATCH, partition_capacity=32)
    (pr,) = rt.partitions.values()
    assert pr.keyed.capacity == 32
    rt.shutdown()


# ------------------------------------------------- the host loop agrees


@pytest.mark.parametrize("agg", ("max", "sum", "avg"))
def test_the_host_loop_gives_the_same_rows(agg, monkeypatch):
    """The same input through the per-key host loop (forced here, by the
    test: the program has no switch) gives the keyed step's rows — up to
    the order within a batch, which the host loop emits key by key."""
    rng = np.random.default_rng(40)
    frames = traffic(rng, frames=3, batch=32)

    def run() -> list:
        d = Deployment(app_text(agg=agg, batch=32), batch=32)
        try:
            for ks, ts in frames:
                d.send(ks, ts)
            return d.rows(), d.partition.keyed
        finally:
            d.rt.shutdown()

    keyed_rows, keyed = run()
    assert keyed is not None
    monkeypatch.setattr(keyed_partition, "keyed_step_refusal",
                        lambda *a, **k: "the test forces the host loop")
    loop_rows, keyed = run()
    assert keyed is None
    assert sorted(loop_rows) == keyed_rows  # the keyed step: arrival order


# ------------------------------------- which partitions take the step


HOST_LOOP = {
    "a range partition": """
        define stream S (k long, x double);
        partition with (x < 5.0 as 'low' or x >= 5.0 as 'high' of S) begin
        from S#window.length(2) select k, max(x) as m insert into Out; end;""",
    "@purge": """
        define stream S (k long, x double);
        @purge(enable='true', interval='1 sec', idle.period='1 sec')
        partition with (k of S) begin
        from S#window.length(2) select k, max(x) as m insert into Out; end;""",
    "two inner queries": """
        define stream S (k long, x double);
        partition with (k of S) begin
        from S#window.length(2) select k, max(x) as m insert into Out;
        from S#window.length(3) select k, min(x) as m insert into Out2;
        end;""",
    "an inner stream": """
        define stream S (k long, x double);
        partition with (k of S) begin
        from S#window.length(2) select k, max(x) as m insert into #Mid;
        from #Mid select k, m insert into Out; end;""",
    "a time window": """
        define stream S (k long, x double);
        partition with (k of S) begin
        from S#window.time(1 sec) select k, max(x) as m insert into Out;
        end;""",
    "a running aggregate": """
        define stream S (k long, x double);
        partition with (k of S) begin
        from S select k, sum(x) as m insert into Out; end;""",
    "a group by": """
        define stream S (k long, g long, x double);
        partition with (k of S) begin
        from S#window.length(2) select k, g, max(x) as m group by g
        insert into Out; end;""",
    "expired events": """
        define stream S (k long, x double);
        partition with (k of S) begin
        from S#window.length(2) select k, max(x) as m
        insert all events into Out; end;""",
    "a float key": """
        define stream S (k double, x double);
        partition with (k of S) begin
        from S#window.length(2) select k, max(x) as m insert into Out; end;""",
    "a long window": """
        define stream S (k long, x double);
        partition with (k of S) begin
        from S#window.length(500) select k, max(x) as m insert into Out;
        end;""",
    "distinctCount": """
        define stream S (k long, x long);
        partition with (k of S) begin
        from S#window.length(4) select k, distinctCount(x) as m
        insert into Out; end;""",
}


@pytest.mark.parametrize("shape", sorted(HOST_LOOP))
def test_everything_else_stays_on_the_host_loop(shape):
    rt = SiddhiManager().create_siddhi_app_runtime(HOST_LOOP[shape],
                                                   batch_size=16)
    (pr,) = rt.partitions.values()
    assert pr.keyed is None
    assert pr.engine_reason
    assert "partitions" not in rt.statistics_report()
    rt.shutdown()


def test_a_stateless_partition_is_neither():
    rt = SiddhiManager().create_siddhi_app_runtime("""
        define stream S (k long, x double);
        partition with (k of S) begin
        from S[x > 1.0] select k, x insert into Out; end;""", batch_size=16)
    (pr,) = rt.partitions.values()
    assert pr.stateless and pr.keyed is None and pr.engine_reason is None
    rt.shutdown()


# ------------------------------------------ the ring is updated in place


def _compiled_step_memory(keys: int):
    """XLA's memory analysis of the keyed step at `keys` key slots."""
    from siddhi_tpu.core.event import EventBatch
    rt = SiddhiManager().create_siddhi_app_runtime(
        app_text(capacity=keys, batch=256), batch_size=256)
    qr = rt.query_runtimes["deviceMax"]
    batch = EventBatch.empty(qr.input_junction.definition, 256)
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        (qr.state, batch, jnp.int64(0)))
    memory = qr._step.lower(*shapes, {}).compile().memory_analysis()
    rt.shutdown()
    return memory


def test_the_steps_temporaries_do_not_grow_with_the_keys():
    """XLA's own account of the compiled step at 2^14 and 2^20 key slots
    (a ring of 128 words a key: 8 MB and 537 MB): arguments and outputs
    follow the ring and the table, which are donated and updated in place;
    temporaries must not."""
    small, large = _compiled_step_memory(2 ** 14), \
        _compiled_step_memory(2 ** 20)
    ring = (2 ** 20 - 2 ** 14) * 128 * 4
    assert large.argument_size_in_bytes - small.argument_size_in_bytes \
        >= ring
    assert large.alias_size_in_bytes >= 2 ** 20 * 128 * 4  # in place
    assert abs(large.temp_size_in_bytes - small.temp_size_in_bytes) \
        < 2 * 2 ** 20, (small, large)


# ------------------------------------------------- snapshot and restore


def test_snapshot_and_restore_mid_stream():
    rng = np.random.default_rng(41)
    frames = traffic(rng, frames=4)
    first = Deployment(app_text())
    try:
        for ks, ts in frames[:2]:
            first.send(ks, ts)
        head = first.rows()
        blob = first.rt.snapshot()
    finally:
        first.rt.shutdown()
    second = Deployment(app_text())
    try:
        second.rt.restore(blob)
        second.sent = 2 * BATCH
        for ks, ts in frames[2:]:
            second.send(ks, ts)
        keys = sum((ks for ks, _ in frames), [])
        temps = sum((ts for _, ts in frames), [])
        want, _ = expected(keys, temps, 10, "max")
        assert head + second.rows() == want
        assert second.report()["partitions"][second.partition.name][
            "keys"] == len(set(keys))
    finally:
        second.rt.shutdown()


def test_a_host_loop_snapshot_is_refused_by_the_keyed_step(monkeypatch):
    from siddhi_tpu.errors import CannotRestoreStateError
    with monkeypatch.context() as m:
        m.setattr(keyed_partition, "keyed_step_refusal",
                  lambda *a, **k: "the test forces the host loop")
        loop = Deployment(app_text())
        loop.send([1, 2, 3], [20.0, 21.0, 22.0])
        loop.rows()
        blob = loop.rt.snapshot()
        loop.rt.shutdown()
    keyed = Deployment(app_text())
    try:
        with pytest.raises(CannotRestoreStateError):
            keyed.rt.restore(blob)
    finally:
        keyed.rt.shutdown()


# ------------------------------------------------ the program and its spans


def test_the_step_is_a_jit_step_cut_into_the_query_familys_stages():
    """`benchmarks/stages.py` finds a query's step by the name `jit_step`
    and sums device time by `siddhi.<stage>`: the keyed step keeps the name
    and books its lookup, gather and write under `siddhi.window`."""
    from siddhi_tpu.core.event import EventBatch
    d = deployment()
    qr = d.rt.query_runtimes["deviceMax"]
    batch = EventBatch.empty(qr.input_junction.definition, BATCH)
    lowered = qr._step.lower(qr.state, batch, jnp.int64(0), {})
    assert lowered.compile().runtime_executable() is not None
    text = lowered.as_text(debug_info=True)
    assert "jit_step" in text or "jit(step)" in text
    for scope in ("siddhi.filter", "siddhi.window/route",
                  "siddhi.window/fetch", "siddhi.window/append",
                  "siddhi.selector", "siddhi.emit"):
        assert scope in text, scope


def test_step_spans_nest_in_the_feeders_dispatch(tmp_path):
    import glob
    import os

    from jax.profiler import ProfileData
    d = deployment()
    d.send([1, 2, 3], [20.0, 21.0, 22.0])  # compiled outside the session
    d.rt.drain()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for f in range(4):
            d.send([f] * BATCH, [20.0] * BATCH)
        d.rt.drain()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("siddhi.partition.step",
                               "siddhi.feeder.dispatch"):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    steps = events["siddhi.partition.step"]
    assert len(steps) == 4
    for a, z in steps:
        assert any(da <= a and z <= dz
                   for da, dz in events["siddhi.feeder.dispatch"])


def test_the_cost_model_prices_the_stated_keys_to_the_byte():
    from siddhi_tpu.analysis.cost import (compute_cost,
                                          measure_runtime_state_bytes)
    text = app_text(capacity=4096)
    predicted = compute_cost(text, batch_size=BATCH)
    rt = SiddhiManager().create_siddhi_app_runtime(text, batch_size=BATCH)
    live = sum(measure_runtime_state_bytes(rt).values())
    rt.shutdown()
    assert predicted.exact
    assert predicted.state_bytes == live == 4096 * 128 * 4 \
        + 512 * 128 * 4 + 12
