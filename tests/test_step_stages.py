"""Step stages (`telemetry/tracing.py` `STEP_STAGES`, `stage`): every step
program names the stages of its family and nothing undeclared, and a scope
changes nothing but debug location — the lowered module without locations is
the unscoped build's byte for byte (which is why the compile cache's key does
not move), and the kernels' outputs are the unscoped functions' bit for bit.

The five query shapes are the benchmark's five configurations at toy sizes.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import query_runtime
from siddhi_tpu.telemetry.tracing import (STAGE_PREFIX, STEP_FAMILIES,
                                          STEP_STAGES, stage)

TRADES = ("define stream cseEventStream (symbol string, price float, "
          "volume long, timestamp long);")
QUOTES = ("define stream quoteEventStream (symbol string, price float, "
          "volume long, timestamp long);")

# shape -> (app, batch, {program name prefix: (family, stages it must name)})
SHAPES = {
    "filter": (f"""
        {TRADES}
        @info(name = 'filt')
        from cseEventStream[700 > price]
        select symbol, price, volume, timestamp insert into outputStream;
        """, 256, {"jit_step": ("query", {"filter", "selector"})}),
    "lengthBatch_groupby": ("""
        define stream MidStream (symbol string, price double, volume long);
        @info(name = 'agg')
        from MidStream#window.lengthBatch(100)
        select symbol, sum(price) as total, avg(price) as avgPrice,
               count() as n
        group by symbol insert into SummaryStream;
        """, 256, {"jit_step": ("query", {
            "window", "window/append", "window/expire", "window/fetch",
            "selector", "selector/sort", "selector/gather", "selector/scan",
            "selector/scatter"})}),
    "time_distinctCount": (f"""
        @app:playback
        {TRADES}
        @info(name = 'distinct')
        @capacity(window = '16384', expire = '1024')
        from cseEventStream#window.time(10 sec)
        select timestamp, distinctCount(symbol) as distinctSymbols
        insert into distinctStream;
        """, 256, {"jit_step": ("query", {
            "window", "window/append", "window/expire", "window/fetch",
            "selector", "selector/sort", "selector/gather", "selector/scan",
            "selector/scatter"})}),
    "rate_limited": (f"""
        {TRADES}
        @info(name = 'limited')
        from cseEventStream[price > 10]
        select symbol, price output last every 4 events
        insert into outputStream;
        """, 64, {"jit_step": ("query", {"filter", "selector", "emit"})}),
    # 4096 lanes x join_max_matches 16: wide enough for the pair compaction
    "two_window_join": (f"""
        {TRADES}
        {QUOTES}
        @info(name = 'join')
        from cseEventStream[price > 1]#window.length(1000) as t
        join quoteEventStream#window.length(1000) as q
        on t.symbol == q.symbol
        select t.symbol as symbol, t.price as tradePrice,
               q.price as quotePrice
        insert into joinedStream;
        """, 4096, {
            "jit_join_probe_left": ("join", {
                "filter", "window", "window/append", "probe", "compact",
                "frames", "selector"}),
            "jit_join_probe_right": ("join", {
                "window", "probe", "compact", "frames", "selector"})}),
    "keyed_pattern": (f"""
        @app:playback
        {TRADES}
        {QUOTES}
        @info(name = 'pattern')
        @capacity(pending = '2048')
        from every t=cseEventStream
            -> q=quoteEventStream[q.symbol == t.symbol] within 5000 sec
        select t.symbol as symbol, t.price as tradePrice,
               q.price as quotePrice
        insert into matchedStream;
        """, 256, {
            "jit_pattern_step_cseEventStream": ("pattern", {
                "filter", "append", "frames", "selector"}),
            "jit_pattern_step_quoteEventStream": ("pattern", {
                "filter", "match", "match/expire", "frames", "selector",
                "emit"}),
            "jit_pattern_heartbeat": ("pattern", {"append", "selector"})}),
    # the SiddhiQL guide's update-or-insert and table join on the device
    # index of a primary key (core/table_index.py)
    "indexed_table": ("""
        define stream DeviceStream (deviceID long, roomNo int, type string);
        define stream TempStream (deviceID long, temp double);
        @PrimaryKey('deviceID') @capacity(rows = '1024')
        define table DeviceTable (deviceID long, roomNo int, type string);
        @info(name = 'register')
        from DeviceStream select deviceID, roomNo, type
        update or insert into DeviceTable on DeviceTable.deviceID == deviceID;
        @info(name = 'enrich')
        from TempStream join DeviceTable
        on TempStream.deviceID == DeviceTable.deviceID
        select TempStream.deviceID as deviceID, DeviceTable.type as roomType,
               TempStream.temp as temp
        having roomType == 'server-room'
        insert into ServerRoomTempStream;
        """, 256, {
            "jit_table_write_DeviceTable": ("table", {
                "table", "table/lookup", "table/order", "table/write"}),
            "jit_join_probe_left": ("join", {
                "table", "table/lookup", "frames", "selector"})}),
}


def _lowered(app: str, batch: int, monkeypatch) -> dict:
    """program name -> (module text without locations, with them) of every
    step program the app warms (a pattern with `within` warms its heartbeat
    step too)."""
    captured: dict = {}

    def capture(jit_fn, *args):
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
            args)
        low = jit_fn.lower(*abstract)
        plain = low.as_text()
        name = re.search(r"module @(\w+)", plain).group(1)
        captured[name] = (plain, low.as_text(debug_info=True))

    monkeypatch.setattr(query_runtime, "aot_warm", capture)
    rt = SiddhiManager().create_siddhi_app_runtime(
        app, batch_size=batch, group_capacity=4096)
    try:
        result = rt.warmup((batch,))
        assert not result.failures, result.failures
    finally:
        rt.shutdown()
    return captured


def _scopes(text_with_locations: str) -> set:
    """Every `siddhi.` scope an operation's location names, as the stage
    (`a` or `a/b`) it stands for."""
    found = set()
    for name in re.findall(r'"(jit\([^"]*)"', text_with_locations):
        parts = name.split("/")
        for i, part in enumerate(parts):
            if not part.startswith(STAGE_PREFIX):
                continue
            top = part[len(STAGE_PREFIX):]
            found.add(top)
            if i + 1 < len(parts) and f"{top}/{parts[i + 1]}" in STEP_STAGES:
                found.add(f"{top}/{parts[i + 1]}")
            elif top not in STEP_STAGES:
                found.add(f"UNDECLARED:{part}")
    return found


def test_the_vocabulary_is_two_levels_and_every_family_speaks_it():
    assert len(set(STEP_STAGES)) == len(STEP_STAGES)
    for name in STEP_STAGES:
        head, _, tail = name.partition("/")
        assert "/" not in tail
        assert head in STEP_STAGES
    for family, stages in STEP_FAMILIES.items():
        assert set(stages) <= set(STEP_STAGES), family
        assert all("/" not in s for s in stages)


@pytest.mark.parametrize("name", ["selector/merge", "Selector", "", "siddhi.filter"])
def test_an_undeclared_stage_raises_where_the_step_is_traced(name):
    def step(x):
        with stage(name):
            return x + 1

    with pytest.raises(ValueError, match="undeclared step stage"):
        jax.jit(step).lower(jnp.int32(0))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_every_step_program_names_its_familys_stages_and_no_other(
        shape, monkeypatch):
    app, batch, programs = SHAPES[shape]
    lowered = _lowered(app, batch, monkeypatch)
    assert set(lowered) >= set(programs), sorted(lowered)
    for program, (family, must) in programs.items():
        found = _scopes(lowered[program][1])
        assert not {s for s in found if s.startswith("UNDECLARED")}, found
        assert found <= set(STEP_STAGES)
        tops = {s.partition("/")[0] for s in found}
        assert tops <= set(STEP_FAMILIES[family]), (program, tops)
        assert must <= found, (program, sorted(must - found))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_scope_is_debug_location_and_the_module_is_the_unscoped_one(
        shape, monkeypatch):
    """What jax hashes for its compile cache is the module stripped of debug
    info: equal text without locations is an equal key."""
    app, batch, programs = SHAPES[shape]
    scoped = _lowered(app, batch, monkeypatch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lowered(app, batch, monkeypatch)
    assert set(scoped) == set(bare)
    for program in programs:
        assert STAGE_PREFIX in scoped[program][1]
        assert STAGE_PREFIX not in bare[program][1]
        assert scoped[program][0] == bare[program][0], program


def _same_bits(a, b) -> None:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _kernel_cases():
    from siddhi_tpu.core.event import EventBatch
    from siddhi_tpu.ops import groupby
    from siddhi_tpu.ops.windows import (LengthBatchWindow, SlidingWindow,
                                        make_layout)
    from siddhi_tpu.query_api.definition import AttributeType
    rng = np.random.default_rng(34)
    L, K = 512, 64
    slots = jnp.asarray(rng.integers(0, K, L), jnp.int32)
    valid = jnp.asarray(rng.random(L) < 0.9)
    resets = jnp.asarray(rng.random(L) < 0.01)
    f32 = jnp.asarray(rng.normal(size=L), jnp.float32)
    i64 = jnp.asarray(rng.integers(-3, 4, L), jnp.int64)
    epoch = jnp.int32(3)
    g64 = groupby.GroupState(
        jnp.asarray(rng.integers(0, 9, K), jnp.int64),
        jnp.asarray(rng.integers(2, 4, K), jnp.int32))
    yield "grouped_scan", lambda: groupby.grouped_scan(
        g64, slots, i64, valid, resets, epoch)
    yield "grouped_scan_max", lambda: groupby.grouped_scan(
        groupby.GroupState(jnp.zeros((K,), jnp.float32), g64.epoch), slots,
        f32, valid, resets, epoch, op="max")
    yield "grouped_scan_fused", lambda: groupby.grouped_scan_fused(
        [jnp.zeros((K,), jnp.float32), g64.values], g64.epoch, slots,
        [f32, i64], valid, resets, epoch)
    one = groupby.GroupState(jnp.asarray([5], jnp.int64),
                             jnp.asarray([3], jnp.int32))
    yield "ungrouped_scan", lambda: groupby.ungrouped_scan(
        one, i64, valid, resets, epoch)
    yield "ungrouped_scan_fused", lambda: groupby.ungrouped_scan_fused(
        [jnp.asarray([0.5], jnp.float32), one.values], one.epoch,
        [f32, i64], valid, resets, epoch)

    B = 64
    layout = make_layout({"symbol": AttributeType.INT,
                          "price": AttributeType.FLOAT})
    batch = EventBatch(
        ts=jnp.asarray(np.arange(B) + 1000, jnp.int64),
        cols={"symbol": jnp.asarray(rng.integers(0, 9, B), jnp.int32),
              "price": jnp.asarray(rng.random(B), jnp.float32)},
        valid=jnp.asarray(rng.random(B) < 0.8),
        types=jnp.zeros((B,), jnp.int8))

    def window_steps(window):
        def run():
            state, outs = window.init_state(), []
            for i in range(3):
                state, chunk = window.step(
                    state, batch, jnp.int64(2000 + 100 * i))
                outs.append(chunk)
            return state, outs
        return run

    yield "SlidingWindow.length", window_steps(
        SlidingWindow(layout, B, length=100))
    yield "SlidingWindow.time", window_steps(
        SlidingWindow(layout, B, time_ms=50, capacity=512, max_expired=128))
    yield "LengthBatchWindow", window_steps(
        LengthBatchWindow(layout, B, 40))


@pytest.mark.parametrize("case", [name for name, _ in _kernel_cases()])
def test_a_kernels_outputs_are_the_unscoped_functions_bit_for_bit(
        case, monkeypatch):
    run = dict(_kernel_cases())[case]
    scoped = jax.jit(run)()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    run = dict(_kernel_cases())[case]
    bare = jax.jit(run)()
    _same_bits(scoped, bare)
