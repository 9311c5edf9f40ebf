"""SXF1 wire-format tests (io/wire.py): framing roundtrip, malformed-input
rejection, the service's binary streams endpoint, and the @map(type='frame')
source mapper. The format is the zero-copy contract between producers and
the ingress pipeline, so the decode side must both reproduce the encoder's
columns exactly and refuse anything that does not match the stream schema.
"""

import json
import os
import struct
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, compiler
from siddhi_tpu import native as native_mod
from siddhi_tpu.io import wire

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEF_TEXT = ("define stream T (symbol string, price double, "
            "volume long, flag bool);")


def _definition():
    return compiler.parse(DEF_TEXT + "\nfrom T select symbol insert into O;"
                          ).stream_definitions["T"]


def _cols(n, seed=3):
    rng = np.random.default_rng(seed)
    syms = np.array([None if i % 9 == 0 else f"S{int(k)}"
                     for i, k in enumerate(rng.integers(1, 20, n))],
                    dtype=object)
    return {
        "symbol": syms,
        "price": rng.uniform(0.5, 900.0, n),
        "volume": rng.integers(1, 1000, n).astype(np.int64),
        "flag": rng.integers(0, 2, n).astype(bool),
    }


class TestRoundtrip:
    def test_plan_shape(self):
        plan = wire.schema_plan(_definition())
        # price is DOUBLE in SiddhiQL but the engine's device dtype is
        # float32 (x64 off) — the wire carries what the device will hold
        assert [(name, code) for name, _dt, code in plan] == [
            ("symbol", "s"), ("price", "f"), ("volume", "l"), ("flag", "b")]

    def test_encode_decode_roundtrip(self):
        plan = wire.schema_plan(_definition())
        cols = _cols(257)
        ts = np.arange(100, 357, dtype=np.int64)
        body = wire.encode_frames(plan, cols, 257, ts=ts)
        frames = list(wire.iter_frames(body))
        assert len(frames) == 1
        got_ts, got, n = wire.decode_frame(frames[0], plan)
        assert n == 257
        np.testing.assert_array_equal(got_ts, ts)
        np.testing.assert_array_equal(
            wire.materialize_strings(got["symbol"]), cols["symbol"])
        np.testing.assert_allclose(got["price"], cols["price"])
        np.testing.assert_array_equal(got["volume"], cols["volume"])
        np.testing.assert_array_equal(got["flag"].astype(bool), cols["flag"])

    def test_chunked_bodies_cover_all_rows(self):
        plan = wire.schema_plan(_definition())
        cols = _cols(500)
        body = wire.encode_frames(plan, cols, 500, chunk=128)
        sizes = []
        seen_syms = []
        for frame in wire.iter_frames(body):
            _ts, got, n = wire.decode_frame(frame, plan)
            sizes.append(n)
            seen_syms.append(wire.materialize_strings(got["symbol"]))
        assert sizes == [128, 128, 128, 116]
        np.testing.assert_array_equal(np.concatenate(seen_syms),
                                      cols["symbol"])

    def test_encoding_is_deterministic(self):
        plan = wire.schema_plan(_definition())
        cols = _cols(100)
        assert wire.encode_frames(plan, cols, 100) == \
            wire.encode_frames(plan, cols, 100)

    def test_numeric_views_are_zero_copy(self):
        plan = wire.schema_plan(_definition())
        cols = _cols(64)
        body = wire.encode_frames(plan, cols, 64)
        frame = next(wire.iter_frames(body))
        _ts, got, _n = wire.decode_frame(frame, plan)
        assert not got["price"].flags.owndata  # a view over the payload

    def test_object_attrs_rejected(self):
        definition = compiler.parse(
            "define stream T (payload object);\n"
            "from T select payload insert into O;"
        ).stream_definitions["T"]
        with pytest.raises(wire.WireFormatError):
            wire.schema_plan(definition)


class TestMalformedInput:
    def _one_frame(self, n=16):
        plan = wire.schema_plan(_definition())
        return plan, wire.encode_frames(plan, _cols(n), n)

    def test_bad_magic(self):
        plan, body = self._one_frame()
        corrupt = bytearray(body)
        corrupt[4:8] = b"NOPE"
        with pytest.raises(wire.WireFormatError, match="magic"):
            for f in wire.iter_frames(bytes(corrupt)):
                wire.decode_frame(f, plan)

    def test_truncated_body(self):
        plan, body = self._one_frame()
        with pytest.raises(wire.WireFormatError, match="truncated"):
            list(wire.iter_frames(body[:-3]))

    def test_truncated_length_prefix(self):
        with pytest.raises(wire.WireFormatError, match="length prefix"):
            list(wire.iter_frames(b"\x01\x02"))

    def test_column_count_mismatch(self):
        plan, body = self._one_frame()
        with pytest.raises(wire.WireFormatError, match="columns"):
            wire.decode_frame(next(wire.iter_frames(body)), plan[:-1])

    def test_typecode_mismatch(self):
        plan, body = self._one_frame()
        swapped = [plan[1], plan[0]] + list(plan[2:])  # symbol <-> price
        with pytest.raises(wire.WireFormatError, match="typecode"):
            wire.decode_frame(next(wire.iter_frames(body)), swapped)


# ---------------------------------------------------- the dictionary block
# One string column's dictionary is decoded by the extension
# (native/columnar.c decode_dict) when it is loaded and by
# wire._decode_dict_py when it is not; the tests below hold the two to the
# same values, offsets and refusals in one process.

needs_extension = pytest.mark.skipif(
    native_mod.native is None, reason="the native extension is not loaded")

STR_PLAN = [("a", np.dtype(np.int32), "s"), ("x", np.dtype("<i8"), "l"),
            ("b", np.dtype(np.int32), "s")]


def _dict_block(entries) -> bytes:
    """dict_n and the entries, each given as str or raw bytes."""
    raws = [e.encode("utf-8") if isinstance(e, str) else e for e in entries]
    return struct.pack("<I", len(raws)) + b"".join(
        struct.pack("<H", len(r)) + r for r in raws)


def _str_frame(n, a, b) -> bytes:
    """A payload for STR_PLAN written by hand (so that a dictionary can
    hold entries no row uses): `a` and `b` are (entries, idx)."""
    parts = [wire.MAGIC, struct.pack("<BHI", 0, 3, n)]
    for code, col in (("s", a), ("l", None), ("s", b)):
        parts.append(code.encode())
        if col is None:
            parts.append(np.arange(n, dtype="<i8").tobytes())
        else:
            parts.append(_dict_block(col[0]))
            parts.append(np.asarray(col[1], dtype="<i4").tobytes())
    return b"".join(parts)


def _idx(n, dict_n, seed=11):
    return np.random.default_rng(seed).integers(-1, dict_n, n)


def _parity_case(case):
    """(n rows, column a, column b), each column (entries, idx); built
    when its test runs, not when the file is collected."""
    if case == "ascii":
        return 50, (["WSO2", "IBM", "S 3"], _idx(50, 3)), (["x"], _idx(50, 1))
    if case == "multibyte_utf8":
        multi = ["z\u00fcrich", "\u6771\u4eac", "\U0001f600", "a\u0301",
                 "\u00e9" * 300]
        return 40, (multi, _idx(40, len(multi))), (["\u00df"], _idx(40, 1))
    if case == "empty_string":
        return 9, (["", "a", ""], _idx(9, 3)), ([""], _idx(9, 1))
    if case == "entry_65535_bytes":
        return 4, (["y" * 0xFFFF, "\u00e9" * 32767 + "z"], [0, 1, -1, 0]), \
            (["t"], [0, 0, 0, 0])
    if case == "dict_n_0":
        return 0, ([], []), ([], [])
    if case == "idx_all_null":
        return 7, (["unused", "too"], [-1] * 7), ([], [-1] * 7)
    if case == "two_string_columns":
        return 64, ([f"A{i}" for i in range(30)], _idx(64, 30, 1)), \
            ([f"B\u00e4{i}" for i in range(17)], _idx(64, 17, 2))
    assert case == "123k_entries"
    big = [f"K{i:07d}" for i in range(123_000)]
    return 131_072, (big, _idx(131_072, len(big))), \
        (["one"], _idx(131_072, 1))


PARITY_CASES = ("ascii", "multibyte_utf8", "empty_string",
                "entry_65535_bytes", "dict_n_0", "idx_all_null",
                "two_string_columns", "123k_entries")


def _decode_with(monkeypatch, decoder, payload):
    """decode_frame over `decoder`; also the end offset of each block."""
    ends = []

    def spy(mv, off, dict_n):
        values, end = decoder(mv, off, dict_n)
        ends.append(end)
        return values, end

    monkeypatch.setattr(wire, "_decode_dict", spy)
    _ts, cols, n = wire.decode_frame(memoryview(payload), STR_PLAN)
    return cols, n, ends


@needs_extension
class TestDictionaryBlock:
    def test_the_extension_is_what_decode_frame_calls(self):
        assert wire._decode_dict is native_mod.native.decode_dict

    @pytest.mark.parametrize("case", PARITY_CASES)
    def test_extension_matches_python_loop(self, case, monkeypatch):
        n, a, b = _parity_case(case)
        payload = _str_frame(n, a, b)
        ext, n_ext, ends_ext = _decode_with(
            monkeypatch, native_mod.native.decode_dict, payload)
        py, n_py, ends_py = _decode_with(
            monkeypatch, wire._decode_dict_py, payload)
        assert n_ext == n_py == n
        assert ends_ext == ends_py and len(ends_ext) == 2
        assert ends_ext[1] == len(payload) - 4 * n  # b's block, then b's idx
        for name, (entries, idx) in (("a", a), ("b", b)):
            kind, values, got_idx = ext[name]
            assert kind == "dict" and type(values) is list
            assert values == py[name][1] == list(entries)
            assert all(type(v) is str for v in values[:100])
            assert got_idx.dtype == np.int32
            np.testing.assert_array_equal(got_idx, py[name][2])
            np.testing.assert_array_equal(got_idx, np.asarray(idx, np.int32))
        np.testing.assert_array_equal(ext["x"], py["x"])

    def test_block_at_an_offset_of_a_bytes_like(self):
        # the function itself, over bytes, bytearray and memoryview
        blob = b"head" + _dict_block(["a", "\u00e9\u00e9", ""])[4:] + b"tail"
        for buf in (blob, bytearray(blob), memoryview(blob)):
            assert native_mod.native.decode_dict(buf, 4, 3) == \
                wire._decode_dict_py(memoryview(buf), 4, 3) == \
                (["a", "\u00e9\u00e9", ""], len(blob) - 4)


def _bad_frames():
    def frame(block: bytes, n=1) -> bytes:
        return b"".join([wire.MAGIC, struct.pack("<BHI", 0, 1, n), b"s",
                         block])

    ok = struct.pack("<H", 2) + b"ok"
    return {
        "entry_header_past_end": (frame(struct.pack("<I", 2) + ok + b"\x05"),
                                  "header runs past"),
        "entry_body_past_end": (frame(struct.pack("<I", 2) + ok
                                      + struct.pack("<H", 10) + b"abc"),
                                "bytes run past"),
        "dict_n_beyond_payload": (frame(struct.pack("<I", 0xFFFFFFFF) + ok
                                        + b"\0" * 4),
                                  "cannot fit"),
        "invalid_utf8": (frame(struct.pack("<I", 1) + struct.pack("<H", 2)
                               + b"\xff\xfe" + b"\0" * 4), "utf-8"),
        "dict_header_past_end": (frame(b"\x01\x00"), "dictionary header"),
    }


BAD = _bad_frames()


class TestDictionaryBlockRefusals:
    """Both decoders refuse the same payloads, as WireFormatError: the
    Python loop used to return a short string for an entry past the end (a
    memoryview slice does not raise) and a bare UnicodeDecodeError."""

    @pytest.mark.parametrize("path", ["extension", "python"])
    @pytest.mark.parametrize("case", list(BAD))
    def test_refused_as_wire_format_error(self, case, path, monkeypatch):
        if path == "extension":
            if native_mod.native is None:
                pytest.skip("the native extension is not loaded")
            decoder = native_mod.native.decode_dict
        else:
            decoder = wire._decode_dict_py
        monkeypatch.setattr(wire, "_decode_dict", decoder)
        payload, said = BAD[case]
        with pytest.raises(wire.WireFormatError, match=said):
            wire.decode_frame(memoryview(payload),
                              [("a", np.dtype(np.int32), "s")])


# ------------------------------------------------ the counter of the decoder

COUNTED_APP = """
@app:name('Counted')
@Async(buffer.size='64', workers='2')
define stream TradeStream (symbol string, price double, volume long);
@info(name='q')
from TradeStream[price < 700.0]
select symbol, price, volume
insert into OutStream;
"""


def _counted_run():
    """Four bodies of three 64-row frames through deliver_frames into an
    @Async pipeline -> (its stats_snapshot(), the output rows)."""
    plan = wire.schema_plan(
        compiler.parse(COUNTED_APP).stream_definitions["TradeStream"])
    rt = SiddhiManager().create_siddhi_app_runtime(COUNTED_APP)
    rows: list = []
    rt.add_callback("OutStream", lambda evs: rows.extend(
        [e.data[0], float(e.data[1]), int(e.data[2])] for e in evs))
    rt.start()
    try:
        handler = rt.get_input_handler("TradeStream")
        for seed in range(4):
            rng = np.random.default_rng(seed)
            cols = {"symbol": np.array(
                        [f"S\u00fc{int(k)}" for k in rng.integers(1, 40, 192)],
                        dtype=object),
                    "price": rng.uniform(1.0, 1000.0, 192),
                    "volume": rng.integers(1, 100, 192).astype(np.int64)}
            body = wire.encode_frames(plan, cols, 192, chunk=64)
            assert wire.deliver_frames(handler, body) == 192
        rt.flush()
        rt.drain()
        snap = rt.junctions["TradeStream"]._pipeline.stats_snapshot()
    finally:
        rt.shutdown()
    return snap, rows


class TestWireNativeFramesCounter:
    @needs_extension
    def test_every_frame_counts_with_the_extension_loaded(self):
        snap, rows = _counted_run()
        assert snap["frames_in"] == 12
        assert snap["wire_native_frames"] == snap["frames_in"]
        assert len(rows) > 0

    def test_zero_without_the_extension_and_the_same_rows(self, tmp_path):
        """SIDDHI_NATIVE=0 decides at import that there is no extension
        (hence the subprocess): the Python loop decodes every frame, the
        counter stays 0 and the output rows are the ones this process
        gets."""
        script = tmp_path / "counted_py.py"
        script.write_text(
            "import json, sys; sys.path.insert(0, %r)\n" % REPO
            + "from siddhi_tpu.util.platform import force_cpu_platform\n"
            "force_cpu_platform(1)\n"
            "import siddhi_tpu.native as native_mod\n"
            "assert not native_mod.available()\n"
            "from siddhi_tpu.io import wire\n"
            "assert wire._decode_dict is wire._decode_dict_py\n"
            "from tests.test_wire_format import _counted_run\n"
            "snap, rows = _counted_run()\n"
            "print('COUNTED ' + json.dumps({'rows': rows, 'frames_in': "
            "snap['frames_in'], 'wire_native_frames': "
            "snap['wire_native_frames']}))\n")
        env = {**os.environ, "SIDDHI_NATIVE": "0", "JAX_PLATFORMS": "cpu"}
        p = subprocess.run([sys.executable, str(script)], env=env,
                           capture_output=True, text=True, timeout=420)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        line = [ln for ln in p.stdout.splitlines()
                if ln.startswith("COUNTED ")][-1]
        theirs = json.loads(line[len("COUNTED "):])
        assert theirs["frames_in"] == 12
        assert theirs["wire_native_frames"] == 0
        _snap, rows = _counted_run()
        assert theirs["rows"] == rows


APP = """
@app:name('WireApp')
define stream TradeStream (symbol string, price double, volume long);
@info(name='q')
from TradeStream[price < 700.0]
select symbol, price, volume
insert into OutStream;
"""


class TestServiceIngestion:
    def _deploy(self):
        from siddhi_tpu.service import SiddhiService
        svc = SiddhiService()
        svc.deploy(APP)
        rt = svc.manager.runtimes["WireApp"]
        got = [0]
        rt.add_callback("OutStream", lambda b: got.__setitem__(
            0, got[0] + b.count), columnar=True)
        return svc, rt, got

    def _body(self, n=200):
        rng = np.random.default_rng(5)
        cols = {
            "symbol": np.array([f"S{int(k)}"
                                for k in rng.integers(1, 10, n)],
                               dtype=object),
            "price": rng.uniform(1.0, 1000.0, n),
            "volume": rng.integers(1, 100, n).astype(np.int64),
        }
        plan = wire.schema_plan(
            compiler.parse(APP).stream_definitions["TradeStream"])
        expected = int((cols["price"] < 700.0).sum())
        return wire.encode_frames(plan, cols, n, chunk=64), expected

    def test_send_frames_delivers(self):
        svc, rt, got = self._deploy()
        try:
            body, expected = self._body()
            assert svc.send_frames("WireApp", "TradeStream", body) == 200
            rt.flush()
            rt.drain()
            assert got[0] == expected
        finally:
            svc.undeploy("WireApp")

    def test_http_frames_endpoint(self):
        svc, rt, got = self._deploy()
        server = svc.make_server(port=0)  # ephemeral port
        port = server.server_address[1]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            body, expected = self._body()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/siddhi-apps/WireApp/streams/"
                "TradeStream", data=body,
                headers={"Content-Type": "application/x-siddhi-frames"})
            with urllib.request.urlopen(req) as resp:
                assert json.loads(resp.read())["accepted"] == 200
            rt.flush()
            rt.drain()
            assert got[0] == expected

            # malformed body → 400, not a 500 traceback
            bad = urllib.request.Request(
                f"http://127.0.0.1:{port}/siddhi-apps/WireApp/streams/"
                "TradeStream", data=b"\x10\x00\x00\x00garbagegarbagegar",
                headers={"Content-Type": "application/x-siddhi-frames"})
            try:
                urllib.request.urlopen(bad)
                raise AssertionError("expected HTTP 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400
        finally:
            server.shutdown()
            svc.undeploy("WireApp")

    def test_json_path_unaffected(self):
        svc, rt, got = self._deploy()
        try:
            n = svc.send("WireApp", "TradeStream",
                         [["S1", 10.0, 5], ["S2", 900.0, 6]])
            assert n == 2
            rt.drain()
            assert got[0] == 1  # 900.0 filtered out
        finally:
            svc.undeploy("WireApp")


class TestFrameSourceMapper:
    def test_mapper_roundtrip(self):
        from siddhi_tpu.io.broker import InMemoryBroker

        app = """
        @app:name('FrameSrc')
        @source(type='inMemory', topic='frames', @map(type='frame'))
        define stream TradeStream (symbol string, price double, volume long);
        @info(name='q')
        from TradeStream select symbol, price, volume insert into OutStream;
        """
        rt = SiddhiManager().create_siddhi_app_runtime(app)
        rows: list = []
        rt.add_callback("OutStream",
                        lambda evs: rows.extend(tuple(e.data) for e in evs))
        rt.start()
        try:
            plan = wire.schema_plan(
                rt.junctions["TradeStream"].definition)
            cols = {
                "symbol": np.array(["A", None, "B"], dtype=object),
                "price": np.array([1.5, 2.5, 3.5]),
                "volume": np.array([10, 20, 30], dtype=np.int64),
            }
            InMemoryBroker.publish("frames",
                                   wire.encode_frames(plan, cols, 3))
            rt.flush()
            rt.drain()
        finally:
            rt.shutdown()
        assert [r[0] for r in rows] == ["A", None, "B"]
        assert [r[2] for r in rows] == [10, 20, 30]
