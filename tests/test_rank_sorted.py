"""`ops/search.py` `rank_sorted32`: the rank of every element of a sorted `v`
in a sorted `a` by one merge, equal to `jnp.searchsorted(a, v, side)` and to
`searchsorted32(a, v, side)` on every shape and on both sides of the rule
that picks the algorithm; and the one site that calls it: the lowered step
of a `time` window at `distinct_60s`'s shapes gathers nothing by the batch's
lanes out of the deadlines, while the steps of the windows that have no time
rule (`length`: the join's; `lengthBatch`: the `agg` step) and the pattern's
do not know the primitive exists.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.ops import search, windows
from siddhi_tpu.ops.search import rank_sorted32, searchsorted32

from .test_step_stages import SHAPES, _lowered

BIG = windows.BIG


def _stamps(rng, n, lo=0, hi=50):
    return np.sort(rng.integers(lo, hi, n)).astype(np.int64)


def _cases():
    rng = np.random.default_rng(35)
    ties = np.array([3, 3, 5, 5, 5, 9], np.int64)
    yield "ties_within_and_across", ties, np.array([3, 5, 5, 9, 9], np.int64)
    yield "runs_of_duplicates", np.repeat(np.int64([1, 4, 4, 7]), 40), \
        np.repeat(np.int64([0, 4, 7, 8]), 25)
    yield "big_tails_in_a", np.concatenate(
        [_stamps(rng, 30), np.full(34, BIG, np.int64)]), _stamps(rng, 20)
    yield "minus_big_heads_in_v", _stamps(rng, 64), np.concatenate(
        [np.full(12, -BIG, np.int64), _stamps(rng, 20)])
    yield "big_against_big", np.int64([1, BIG, BIG]), np.int64([-BIG, 1, BIG])
    yield "differ_only_above_bit_32", _stamps(rng, 50, 0, 8) << 32, \
        _stamps(rng, 40, 0, 8) << 32
    yield "differ_only_below_bit_32", (1 << 40) + (_stamps(rng, 50) << 20), \
        (1 << 40) + (_stamps(rng, 40) << 20)
    yield "low_word_past_its_sign_bit", np.int64(
        [0x7FFFFFFF, 0x80000000, 0xFFFFFFFF, 0x100000000]), np.int64(
        [0x7FFFFFFF, 0x80000000, 0x80000001, 0xFFFFFFFF, 0x100000000])
    yield "negative_stamps", _stamps(rng, 60, -(1 << 40), 1 << 40), \
        _stamps(rng, 45, -(1 << 40), 1 << 40)
    yield "n_is_zero", np.zeros(0, np.int64), _stamps(rng, 7)
    yield "b_is_one", _stamps(rng, 33), np.int64([25])
    yield "n_is_one", np.int64([25]), _stamps(rng, 33)
    yield "e1500_b700", _stamps(rng, 1500, 0, 900), _stamps(rng, 700, 0, 900)
    yield "e1024_b4096", _stamps(rng, 1024, 0, 2000), \
        _stamps(rng, 4096, 0, 2000)
    yield "int32_keys", _stamps(rng, 90).astype(np.int32), \
        _stamps(rng, 70).astype(np.int32)
    yield "float32_keys", _stamps(rng, 90).astype(np.float32) / 4, \
        _stamps(rng, 70).astype(np.float32) / 4


CASES = {name: (a, v) for name, a, v in _cases()}


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_merge_equals_both_searches(name, side):
    a, v = CASES[name]
    want = np.searchsorted(a, v, side=side)
    ja, jv = jnp.asarray(a), jnp.asarray(v)
    assert ja.dtype == a.dtype  # 64-bit stamps stay 64-bit
    for fn in (rank_sorted32, search._merge_ranks):
        got = jax.jit(fn, static_argnames="side")(ja, jv, side=side)
        assert got.dtype == jnp.int32 and got.shape == v.shape
        assert np.array_equal(np.asarray(got), want), fn.__name__
    assert np.array_equal(np.asarray(searchsorted32(ja, jv, side=side)), want)
    assert np.array_equal(
        np.asarray(jnp.searchsorted(ja, jv, side=side)), want)


def _sorts_and_gathers(n: int, b: int) -> tuple:
    text = jax.jit(rank_sorted32, static_argnames="side").lower(
        jax.ShapeDtypeStruct((n,), jnp.int64),
        jax.ShapeDtypeStruct((b,), jnp.int64), side="right").as_text()
    return text.count("stablehlo.sort"), text.count("stablehlo.gather")


def test_the_algorithm_follows_the_static_shapes_alone():
    """On each side of the crossover, from nothing but `(N, B)`: the search
    where a few clocks meet many deadlines, the merge where the batch is
    wide (the cell's shapes), and equal answers at both."""
    few, wide = (524288, 1024), (524288, 131072)
    assert not search._merge_beats_search(*few)
    assert search._merge_beats_search(*wide)
    sorts, gathers = _sorts_and_gathers(*few)
    assert sorts == 0 and gathers >= 20
    sorts, gathers = _sorts_and_gathers(*wide)
    assert sorts >= 1 and gathers == 0
    rng = np.random.default_rng(7)
    for n, b in ((4096, 16), (4096, 4096)):
        a, v = _stamps(rng, n, 0, 3000), _stamps(rng, b, 0, 3000)
        got = jax.jit(rank_sorted32, static_argnames="side")(
            jnp.asarray(a), jnp.asarray(v), side="right")
        assert np.array_equal(np.asarray(got),
                              np.searchsorted(a, v, side="right"))


# ------------------------------------------------ the one site that calls it

STEP_APP = """
{playback}
define stream S (symbol string, price float, volume long, timestamp long);
@info(name = 'q')
{capacity}
from S#window.{window}
select timestamp, count() as c insert into O;
"""


def _steps(window, playback, capacity, batch) -> tuple:
    """Thirty steps of the window alone over seeded batches — full, partial
    and empty ones, stamps in and out of order, frames that tie the deadlines
    of the frame before, clocks that jump past the expiry width: every
    state and every chunk, and the last state."""
    rt = SiddhiManager().create_siddhi_app_runtime(
        STEP_APP.format(playback=playback, capacity=capacity, window=window),
        batch_size=batch, group_capacity=64)
    try:
        qr = rt.query_runtimes["q"]
        empty = EventBatch.empty(qr.input_junction.definition, batch)
        step = jax.jit(qr.window.step)
        state, rng, base, out = qr.window.init_state(), \
            np.random.default_rng(35), 0, []
        for _ in range(30):
            n = int(rng.choice([0, 1, batch // 3, batch - 1, batch]))
            base += int(rng.choice([
                rng.integers(0, 400), -rng.integers(0, 900), 1000,
                rng.integers(2000, 9000)]))
            ts = base + rng.integers(0, 300, batch)
            if rng.random() < 0.7:
                ts = np.sort(ts)
            ts = jnp.asarray(ts, jnp.int64)
            state, chunk = step(state, dataclasses.replace(
                empty, ts=ts, cols={**empty.cols, "timestamp": ts},
                valid=jnp.arange(batch) < n), jnp.int64(base + 300))
            out.append(jax.tree_util.tree_map(np.asarray, (state, chunk)))
        return jax.tree_util.tree_leaves(out), state
    finally:
        rt.shutdown()


@pytest.mark.parametrize("window, playback, capacity, batch", [
    ("time(1 sec)", "@app:playback",
     "@capacity(window='256', expire='48')", 64),
    ("time(1 sec)", "", "@capacity(window='256', expire='48')", 64),
    ("time(1 sec)", "@app:playback",
     "@capacity(window='2048', expire='1024')", 256),
    ("timeLength(1 sec, 40)", "@app:playback", "@capacity(expire='40')", 64),
    ("externalTime(timestamp, 1 sec)", "",
     "@capacity(window='256', expire='12')", 16),
    ("delay(1 sec)", "@app:playback",
     "@capacity(window='256', expire='48')", 64),
], ids=["time_playback", "time_wall_clock", "time_wide", "timeLength",
        "externalTime", "delay"])
def test_the_step_with_the_merge_is_the_step_with_the_search(
        window, playback, capacity, batch, monkeypatch):
    monkeypatch.setattr(windows, "rank_sorted32", search._merge_ranks)
    merged, last = _steps(window, playback, capacity, batch)
    monkeypatch.setattr(windows, "rank_sorted32", searchsorted32)
    searched, _ = _steps(window, playback, capacity, batch)
    assert int(last.expired) > batch and int(last.deferred) > 0  # it bit
    assert len(merged) == len(searched)
    for got, want in zip(merged, searched):
        assert got.dtype == want.dtype and np.array_equal(got, want)



CELL_B, CELL_E = 131072, 524288
TIME_APP = f"""
@app:playback
define stream cseEventStream (symbol string, price float, volume long,
                              timestamp long);
@info(name = 'distinct')
@capacity(window = '{CELL_E}', expire = '{CELL_E}')
from cseEventStream#window.time(60000 sec)
select timestamp, distinctCount(symbol) as distinctSymbols
insert into distinctStream;
"""


def _lowered_time_step() -> str:
    """The `time` window's step at the cell's batch and expiry width, the
    ring as small as they allow (16 MB), lowered from shapes: no compile."""
    rt = SiddhiManager().create_siddhi_app_runtime(
        TIME_APP, batch_size=CELL_B, group_capacity=4096)
    try:
        qr = rt.query_runtimes["distinct"]
        assert (qr.window.E, qr.window.chunk_width) == (
            CELL_E, CELL_B + CELL_E)
        batch = EventBatch.empty(qr.input_junction.definition, CELL_B)
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
            (qr.state, batch, jnp.int64(0)))
        return qr._step.lower(*shapes, {}).as_text(debug_info=True)
    finally:
        rt.shutdown()


def _ops_under(text: str, op: str, scope: str) -> list:
    """The type signatures of every `op` whose location names `scope`."""
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    found = []
    for line in text.splitlines():
        if f"stablehlo.{op}" not in line:
            continue
        loc = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if loc and scope in named.get(loc.group(1), ""):
            found.append(line[line.rindex(" : "):])
    return found


def _takes_batch_lanes_out_of_the_deadlines(signature: str) -> bool:
    operand, indices = re.match(
        r" : \(tensor<([^>]*)>, tensor<([^>]*)>\)", signature).groups()
    return operand.startswith(f"{CELL_E}x") and indices.startswith(
        f"{CELL_B}x")


def test_the_time_rule_gathers_nothing_by_batch_lane_out_of_the_deadlines(
        monkeypatch):
    scope = "siddhi.window/expire"
    text = _lowered_time_step()
    gathers = _ops_under(text, "gather", scope)
    assert not [g for g in gathers
                if _takes_batch_lanes_out_of_the_deadlines(g)], gathers
    assert f"{scope}/sort" in text  # the merge, where the search was
    # the reader reads: the search in its place shows its twenty rounds
    monkeypatch.setattr(windows, "rank_sorted32", searchsorted32)
    gathers = _ops_under(_lowered_time_step(), "gather", scope)
    assert len([g for g in gathers
                if _takes_batch_lanes_out_of_the_deadlines(g)]) == 20


@pytest.mark.parametrize("shape", ["lengthBatch_groupby", "two_window_join",
                                   "keyed_pattern", "filter"])
def test_steps_without_a_time_rule_do_not_know_the_primitive(
        shape, monkeypatch):
    """The `agg` step, the join's two `length` windows, the pattern's and the
    filter's steps lower to the same text with the primitive taken away: no
    cell's program but `distinct_60s`'s can have changed with it."""
    app, batch, programs = SHAPES[shape]
    with_it = _lowered(app, batch, monkeypatch)

    def gone(*args, **kw):
        raise AssertionError("a step without a time rule ranked clocks")
    monkeypatch.setattr(windows, "rank_sorted32", gone)
    monkeypatch.setattr(search, "rank_sorted32", gone)
    without = _lowered(app, batch, monkeypatch)
    assert set(with_it) == set(without) >= set(programs)
    for name in with_it:
        assert with_it[name][0] == without[name][0], name
        assert "siddhi.window/expire/sort" not in with_it[name][1]  # no merge
