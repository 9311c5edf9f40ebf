"""Native C marshaller tests — parity with the pure-Python encoder and shared
string interning (native/columnar.c, loaded via siddhi_tpu/native.py)."""

import numpy as np
import pytest

from siddhi_tpu import native
from siddhi_tpu.core.event import StreamCodec, StringTable
from siddhi_tpu.query_api.definition import Attribute, AttributeType, StreamDefinition

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native extension not built")

DEF = StreamDefinition(id="S", attributes=(
    Attribute("sym", AttributeType.STRING),
    Attribute("price", AttributeType.DOUBLE),
    Attribute("vol", AttributeType.LONG),
    Attribute("n", AttributeType.INT),
    Attribute("f", AttributeType.FLOAT),
    Attribute("ok", AttributeType.BOOL),
))

ROWS = [
    ("IBM", 75.5, 100, 3, 1.5, True),
    ("WSO2", 57.25, 10, -2, -0.5, False),
    (None, None, None, None, None, None),
    ("IBM", 0.0, 2**40, 7, 9.0, True),
]


def _codec(force_python=False):
    shared = StringTable()
    codec = StreamCodec(DEF, shared)
    if force_python:
        codec._native_plan = None
    return codec, shared


class TestNativeEncoder:
    def test_parity_with_python_encoder(self):
        c_native, s1 = _codec()
        c_python, s2 = _codec(force_python=True)
        assert c_native._native_plan is not None
        a = c_native.rows_to_columns(ROWS, n_pad=8)
        b = c_python.rows_to_columns(ROWS, n_pad=8)
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
        assert s1.snapshot() == s2.snapshot()

    def test_interning_shared_with_python_table(self):
        codec, shared = _codec()
        pre = shared.encode("IBM")  # interned via the PYTHON path first
        cols = codec.rows_to_columns(ROWS, n_pad=4)
        assert cols["sym"][0] == pre  # native reused the same code
        assert cols["sym"][2] == 0  # null
        assert shared.decode(int(cols["sym"][1])) == "WSO2"

    def test_restore_keeps_native_plan_wired(self):
        codec, shared = _codec()
        codec.rows_to_columns(ROWS, n_pad=4)
        snap = shared.snapshot()
        shared.restore(snap)
        cols = codec.rows_to_columns([("IBM", 1.0, 1, 1, 1.0, True)], n_pad=2)
        assert shared.decode(int(cols["sym"][0])) == "IBM"

    def test_fill_ts_monotone_pad(self):
        out = np.zeros(6, dtype=np.int64)
        native.native.fill_ts([5, 7, 9], out, 6)
        assert out.tolist() == [5, 7, 9, 9, 9, 9]

    def test_throughput_improvement(self):
        # not a strict benchmark — just assert the native path isn't slower
        import time
        rows = [(f"S{i % 100}", float(i), i, i, float(i), True)
                for i in range(20_000)]
        c_native, _ = _codec()
        c_python, _ = _codec(force_python=True)
        t0 = time.perf_counter()
        c_native.rows_to_columns(rows)
        t_native = time.perf_counter() - t0
        t0 = time.perf_counter()
        c_python.rows_to_columns(rows)
        t_python = time.perf_counter() - t0
        assert t_native < t_python


# ------------------------------------------------- the byte-keyed intern table
#
# StringTable.intern_array through the extension (pointer memo, byte-keyed
# table probed off the interpreter lock, the dict behind both) against the
# pure-Python loop: same codes, same snapshot, in the same order of calls.


def _fresh(values):
    """New str objects equal to `values`: what a wire frame's dictionary
    is each frame, so the pointer memo cannot answer for them."""
    return [None if v is None else (v + "x")[:-1] for v in values]


def _universe(kind: str, n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    ids = rng.permutation(n).tolist()
    if kind == "ascii":
        return [f"S{i:07d}" for i in ids]
    if kind == "long":  # past the slot's inline bytes: the key arena
        return [f"symbol-{i:07d}-of-a-longer-universe" for i in ids]
    if kind == "non_ascii":
        return [f"Ωμέγα-{i}-日本" for i in ids]
    # mixed: empty, None and a lone surrogate (not UTF-8: never a key)
    out = [f"m{i}" if i % 3 else f"ü{i}" for i in ids]
    out[::97] = [None] * len(out[::97])
    out[5::101] = [""] * len(out[5::101])
    out[7::211] = ["\ud800lone"] * len(out[7::211])
    return out


def _calls(kind: str, per_call: int, seed: int) -> list:
    """Repeated calls drawing from one universe of 5,000 values (enough
    to double the table from its first 1,024 slots several times)."""
    universe = _universe(kind, 5000, seed)
    rng = np.random.default_rng(seed + 1)
    return [[universe[i] for i in rng.integers(0, len(universe), per_call)]
            for _ in range(6)]


@pytest.mark.parametrize("per_call", [300, 4000],
                         ids=["interpreter_held", "interpreter_released"])
@pytest.mark.parametrize("kind", ["ascii", "long", "non_ascii", "mixed"])
def test_table_codes_and_snapshot_match_the_python_loop(kind, per_call,
                                                        monkeypatch):
    calls = _calls(kind, per_call, seed=len(kind) * 1000 + per_call)
    native_tbl, python_tbl = StringTable(), StringTable()
    got = [native_tbl.intern_array(_fresh(c)) for c in calls]
    with monkeypatch.context() as m:
        m.setattr(native, "native", None)
        want = [python_tbl.intern_array(list(c)) for c in calls]
    for (codes, n, hits), (ref, n_ref, hits_ref) in zip(got, want):
        np.testing.assert_array_equal(codes, ref)
        assert (n_ref, hits_ref) == (0, 0)  # no extension: no counts
        assert n == len(ref) and 0 <= hits <= n
    assert native_tbl.snapshot() == python_tbl.snapshot()
    # the later calls find what the first ones interned
    assert got[-1][2] > 0


def test_table_gives_a_string_first_interned_by_encode_its_code():
    tbl = StringTable()
    pre = tbl.encode("IBM")
    codes, _, hits = tbl.intern_array(_fresh(["WSO2", "IBM"]))
    assert codes.tolist() == [pre + 1, pre] and hits == 0
    codes, _, hits = tbl.intern_array(_fresh(["IBM", "WSO2"] * 600))
    assert codes.tolist() == [pre, pre + 1] * 600 and hits == 1200


def test_live_transient_uuid_keeps_its_code_and_never_enters_the_table():
    tbl = StringTable()
    uuid = "0b9d6c1e-3f7a-4c2e-9a51-7d3e2b8f6a10"
    tcode = tbl.encode_transient(uuid, capacity=2)
    assert tcode >= StringTable.TRANSIENT_BASE
    for _ in range(2):  # a second call would hit a cached code
        codes, n, hits = tbl.intern_array(_fresh([uuid] * 1500))
        assert set(codes.tolist()) == {tcode} and (n, hits) == (1500, 0)
    # once recycled out of the ring the uuid is an ordinary string: a code
    # cached from its transient life would come back here
    tbl.encode_transient("u-2", capacity=2)
    tbl.encode_transient("u-3", capacity=2)
    codes, _, hits = tbl.intern_array(_fresh([uuid]))
    assert codes.tolist() == [tbl.encode(uuid)] and hits == 0
    assert codes[0] < StringTable.TRANSIENT_BASE


def test_restore_of_an_older_snapshot_drops_the_table(monkeypatch):
    values = [f"k{i}" for i in range(3000)]
    tbl = StringTable()
    tbl.intern_array(_fresh(values[:10]))
    older = tbl.snapshot()
    tbl.intern_array(_fresh(values))  # the table now caches k10.. as 11..
    tbl.restore(older)
    later = list(reversed(values))
    codes, _, hits = tbl.intern_array(_fresh(later))
    ref_tbl = StringTable()
    ref_tbl.restore(older)
    with monkeypatch.context() as m:
        m.setattr(native, "native", None)
        ref, _, _ = ref_tbl.intern_array(later)
    np.testing.assert_array_equal(codes, ref)
    assert hits == 0  # a new table: nothing cached from before the restore
    assert tbl.snapshot() == ref_tbl.snapshot()


def test_intern_counters_count_exactly_the_values_and_the_hits():
    tbl = StringTable()
    first = [f"v{i}" for i in range(2000)]
    codes, n, hits = tbl.intern_array(_fresh(first))
    assert (n, hits) == (2000, 0)  # every value new: resolved by the dict
    # 1,500 known values, 500 new ones and 100 nulls
    second = first[:1500] + [f"w{i}" for i in range(500)] + [None] * 100
    codes, n, hits = tbl.intern_array(_fresh(second))
    assert (n, hits) == (2100, 1500)
    assert codes[-100:].tolist() == [0] * 100
    # the very same objects again: the pointer memo answers for the values
    # the dict resolved, before the table is asked
    same = [f"z{i}" for i in range(50)]
    assert tbl.intern_array(same)[1:] == (50, 0)
    assert tbl.intern_array(same)[1:] == (50, 0)
    assert tbl.intern_array(_fresh(same))[1:] == (50, 50)
