"""SXF1 — the zero-copy binary wire format for columnar stream frames.

The JSON ingestion path (`POST .../streams/<s>` with {"events": [[...]]})
decodes every row into Python objects before the engine re-encodes them into
columns — exactly the per-row host work the ingress pipeline exists to
avoid. SXF1 carries the columns themselves: a length-prefixed frame whose
numeric payloads are raw little-endian arrays that `np.frombuffer` views
without copying, and whose string columns are dictionary-encoded (distinct
values + int32 indexes), so the server interns per DISTINCT value instead of
per row and the indexes map onto ring slots untouched.

Framing (all integers little-endian):

    body    := frame*
    frame   := u32 payload_len | payload
    payload := 'SXF1' | u8 flags | u16 n_cols | u32 n_rows
               | [ i64 ts[n_rows]          when flags bit0 (has_ts) ]
               | col*
    col     := u8 typecode | coldata
    coldata := raw values, width(typecode) * n_rows      (b i l f d)
             | u32 dict_n
               | dict_n * (u16 byte_len | utf8 bytes)    (s: dictionary)
               | i32 idx[n_rows]                         (-1 = null)

Type codes match native/columnar.c: b=1 byte (bool/int8), i=int32,
l=int64, f=float32, d=float64, s=string (dictionary + int32 indexes).
Columns appear in stream-attribute declaration order; OBJECT attributes are
not representable. Numeric nulls are the engine's null sentinels
(core/dtypes.null_value), encoded by the producer.

The decoder returns numpy VIEWS over the request buffer for numeric columns
and ('dict', values, idx_view) triples for strings — the form
IngressPipeline.submit_columns consumes directly. Without a pipeline the
same frame materializes through the ordinary send_columns path, so the two
ingestion modes stay byte-identical downstream.
"""

from __future__ import annotations

import struct
from typing import Iterator, Optional, Sequence

import numpy as np

from .. import native as native_mod
from ..telemetry.tracing import Span

MAGIC = b"SXF1"
FLAG_HAS_TS = 0x01

#: typecode -> (byte width, little-endian numpy dtype for the raw payload)
_WIRE_DTYPES = {
    "b": (1, np.dtype("u1")),
    "i": (4, np.dtype("<i4")),
    "l": (8, np.dtype("<i8")),
    "f": (4, np.dtype("<f4")),
    "d": (8, np.dtype("<f8")),
}

_NP_TYPECODE = {"bool": "b", "int8": "b", "int32": "i", "int64": "l",
                "float32": "f", "float64": "d"}


class WireFormatError(ValueError):
    pass


def schema_plan(definition) -> list[tuple[str, np.dtype, str]]:
    """Per-attribute (name, host dtype, wire typecode) in declaration
    order. Raises for schemas SXF1 cannot carry (OBJECT attrs)."""
    from ..core import dtypes as _dt
    from ..query_api.definition import AttributeType
    import jax.numpy as jnp

    plan = []
    for a in definition.attributes:
        if a.type == AttributeType.OBJECT:
            raise WireFormatError(
                f"stream {definition.id!r}: OBJECT attribute {a.name!r} "
                "has no columnar wire representation")
        if a.type == AttributeType.STRING:
            plan.append((a.name, np.dtype(np.int32), "s"))
            continue
        dt = np.dtype(jnp.dtype(_dt.device_dtype(a.type)).name)
        code = _NP_TYPECODE.get(dt.name)
        if code is None:  # pragma: no cover — no such scalar type today
            raise WireFormatError(f"unsupported dtype {dt} for {a.name!r}")
        plan.append((a.name, dt, code))
    return plan


# ------------------------------------------------------------------ encoding


def encode_frame(plan: Sequence[tuple[str, np.dtype, str]],
                 columns: dict, n: int,
                 ts: Optional[np.ndarray] = None) -> bytes:
    """Encode one frame. String columns accept str/None sequences (object
    arrays) — dictionary-encoded here, producer-side, so the server never
    sees per-row strings."""
    parts = [MAGIC,
             struct.pack("<BHI", FLAG_HAS_TS if ts is not None else 0,
                         len(plan), n)]
    if ts is not None:
        ts = np.ascontiguousarray(np.asarray(ts)[:n], dtype="<i8")
        parts.append(ts.tobytes())
    for name, dt, code in plan:
        if name not in columns:
            raise WireFormatError(f"encode_frame: missing column {name!r}")
        src = columns[name]
        if code == "s":
            arr = np.asarray(src, dtype=object)[:n]
            # first-appearance dictionary: deterministic, so re-encoding
            # the same rows yields the same bytes
            dict_pos: dict[str, int] = {}
            idx = np.empty(n, dtype="<i4")
            for i, v in enumerate(arr):
                if v is None:
                    idx[i] = -1
                    continue
                p = dict_pos.get(v)
                if p is None:
                    p = len(dict_pos)
                    dict_pos[v] = p
                idx[i] = p
            parts.append(struct.pack("<BI", ord(code), len(dict_pos)))
            for v in dict_pos:
                raw = v.encode("utf-8")
                if len(raw) > 0xFFFF:
                    raise WireFormatError(
                        f"string value too long for SXF1 ({len(raw)} bytes)")
                parts.append(struct.pack("<H", len(raw)))
                parts.append(raw)
            parts.append(idx.tobytes())
        else:
            width, wdt = _WIRE_DTYPES[code]
            raw = np.ascontiguousarray(np.asarray(src)[:n], dtype=dt)
            if raw.dtype.itemsize != width:  # pragma: no cover — plan bug
                raise WireFormatError(f"width mismatch for {name!r}")
            parts.append(struct.pack("<B", ord(code)))
            parts.append(raw.astype(wdt, copy=False).tobytes())
    payload = b"".join(parts)
    return struct.pack("<I", len(payload)) + payload


def encode_frames(plan, columns: dict, n: int,
                  ts: Optional[np.ndarray] = None,
                  chunk: Optional[int] = None) -> bytes:
    """Encode `n` rows as one frame, or as ceil(n/chunk) frames when
    `chunk` is given (multi-frame bodies exercise streaming decode)."""
    if chunk is None or chunk >= n:
        return encode_frame(plan, columns, n, ts)
    out = []
    for s in range(0, n, chunk):
        m = min(chunk, n - s)
        cols_c = {k: np.asarray(v)[s:s + m] for k, v in columns.items()}
        ts_c = None if ts is None else np.asarray(ts)[s:s + m]
        out.append(encode_frame(plan, cols_c, m, ts_c))
    return b"".join(out)


# ------------------------------------------------------------------ decoding


def iter_frames(body) -> Iterator[memoryview]:
    """Yield each frame's payload as a memoryview (no copies)."""
    mv = memoryview(body)
    off = 0
    total = len(mv)
    while off < total:
        if total - off < 4:
            raise WireFormatError("truncated frame length prefix")
        (plen,) = struct.unpack_from("<I", mv, off)
        off += 4
        if total - off < plen:
            raise WireFormatError(
                f"truncated frame: need {plen} bytes, have {total - off}")
        yield mv[off:off + plen]
        off += plen


def _decode_dict_py(mv: memoryview, off: int,
                    dict_n: int) -> tuple[list, int]:
    """One string column's dictionary block without the extension:
    `dict_n` entries of (u16 byte_len | utf8 bytes) from `off` in `mv` ->
    (values: list[str], offset after the last entry). Same bounds, same
    refusals (ValueError) as native/columnar.c decode_dict."""
    total = len(mv)
    if off < 0 or not 0 <= dict_n <= (total - off) // 2:
        # an entry is at least its two-byte header
        raise ValueError(
            f"dictionary of {dict_n} entries cannot fit in the "
            f"{total - off} bytes after offset {off}")
    values: list = []
    for i in range(dict_n):
        if total - off < 2:
            raise ValueError(f"dictionary entry {i} of {dict_n}: header "
                             "runs past the end of the payload")
        (blen,) = struct.unpack_from("<H", mv, off)
        off += 2
        if total - off < blen:  # a memoryview slice would come back short
            raise ValueError(f"dictionary entry {i} of {dict_n}: {blen} "
                             "bytes run past the end of the payload")
        values.append(str(mv[off:off + blen], "utf-8"))
        off += blen
    return values, off


#: a dictionary block's decoder: the extension's loop when it is loaded
#: (decided at import, like the ring and intern_column), else the one above
_decode_dict = (native_mod.native.decode_dict
                if native_mod.native is not None else _decode_dict_py)


def decode_frame(payload: memoryview, plan) -> tuple[
        Optional[np.ndarray], dict, int]:
    """Decode one payload against `plan`. Returns (ts or None, columns, n)
    where numeric columns are zero-copy views over the payload and string
    columns are ('dict', values: list[str], idx: int32 view) triples —
    exactly what IngressPipeline.submit_columns takes."""
    mv = memoryview(payload)
    if len(mv) < 11 or bytes(mv[:4]) != MAGIC:
        raise WireFormatError("bad frame magic (want 'SXF1')")
    flags, n_cols, n = struct.unpack_from("<BHI", mv, 4)
    off = 11
    if n_cols != len(plan):
        raise WireFormatError(
            f"frame has {n_cols} columns, stream declares {len(plan)}")
    ts = None
    if flags & FLAG_HAS_TS:
        end = off + 8 * n
        if len(mv) < end:
            raise WireFormatError("truncated timestamp block")
        ts = np.frombuffer(mv[off:end], dtype="<i8")
        off = end
    cols: dict = {}
    for name, dt, code in plan:
        if len(mv) < off + 1:
            raise WireFormatError(f"truncated column header for {name!r}")
        got = chr(mv[off])
        off += 1
        if got != code:
            raise WireFormatError(
                f"column {name!r}: frame typecode {got!r} != schema {code!r}")
        if code == "s":
            if len(mv) < off + 4:
                raise WireFormatError(
                    f"truncated dictionary header for {name!r}")
            (dict_n,) = struct.unpack_from("<I", mv, off)
            try:
                values, off = _decode_dict(mv, off + 4, dict_n)
            except ValueError as e:  # a bound, or bytes that are not UTF-8
                raise WireFormatError(f"column {name!r}: {e}") from e
            end = off + 4 * n
            if len(mv) < end:
                raise WireFormatError(f"truncated index block for {name!r}")
            idx = np.frombuffer(mv[off:end], dtype="<i4")
            off = end
            cols[name] = ("dict", values, idx)
        else:
            width, wdt = _WIRE_DTYPES[code]
            end = off + width * n
            if len(mv) < end:
                raise WireFormatError(f"truncated data block for {name!r}")
            raw = np.frombuffer(mv[off:end], dtype=wdt)
            cols[name] = raw if raw.dtype == dt else raw.view(dt) \
                if raw.dtype.itemsize == dt.itemsize else raw.astype(dt)
            off = end
    return ts, cols, n


def materialize_strings(col) -> np.ndarray:
    """('dict', values, idx) -> object array of str/None (the fallback
    path's send_columns input)."""
    _, values, idx = col
    lut = np.empty(len(values) + 1, dtype=object)
    lut[0] = None
    lut[1:] = values
    return lut[idx.astype(np.int64) + 1]


def subset_dict_column(values, idx, sel) -> tuple:
    """A ('dict', values, idx) column restricted to boolean mask `sel`,
    with the value list COMPACTED to just the entries the surviving rows
    reference — the shard router's pre-interning subset: a shard's string
    table interns only the keys routed to it, never the whole frame
    dictionary."""
    idx = np.asarray(idx)
    sub = idx[sel]
    valid = sub >= 0
    used = np.unique(sub[valid]) if valid.any() else \
        np.zeros(0, dtype=np.int64)
    remap = np.full(len(values), -1, dtype=np.int32)
    remap[used] = np.arange(len(used), dtype=np.int32)
    new_idx = np.where(valid, remap[np.clip(sub, 0, None)],
                       np.int32(-1)).astype(np.int32)
    return ("dict", [values[int(i)] for i in used], new_idx)


def deliver_frames(handler, body) -> int:
    """Decode every frame in `body` and feed it through `handler`'s
    junction: straight into the ingress pipeline when one is running
    (zero-copy: numeric views + dictionary interning per distinct value),
    else through the ordinary send_columns path. Returns rows accepted.

    A handler carrying its own `deliver_frames` (the shard plane's routing
    handler) owns the whole decode-route-deliver sequence: frames are
    hashed on ORIGINAL dictionary values and split per shard BEFORE any
    interning."""
    if hasattr(handler, "deliver_frames"):
        return handler.deliver_frames(body)
    j = handler.junction
    plan = schema_plan(j.definition)
    total = 0
    by_extension = _decode_dict is not _decode_dict_py
    for payload in iter_frames(body):
        # the decode runs in the HTTP handler's thread and holds the
        # interpreter while it makes a `str` per dictionary entry (one call
        # into the extension, or the Python loop without it): the pipeline
        # books it as its `wire` stage and counts which of the two it was
        with Span("siddhi.front.wire", cpu=True) as decode:
            ts, cols, n = decode_frame(payload, plan)
        if n == 0:
            continue
        if ts is None:
            now = j.ctx.timestamp_generator.current_time()
            ts = np.full(n, now, dtype=np.int64)
        p = j._pipeline
        if p is not None and j.wal is None and not j.taps \
                and not j._lock_owned():
            j.ctx.timestamp_generator.observe_event_time(int(ts[:n].max()))
            done = p.submit_columns(ts, cols, n, frame=decode,
                                    frame_native=by_extension)
            if done >= n:
                total += n
                continue
            # pipeline stopping: remainder through the synchronous path
            ts = ts[done:]
            cols = {k: (v if isinstance(v, tuple) else v[done:])
                    for k, v in cols.items()}
            cols = {k: (("dict", v[1], v[2][done:])
                        if isinstance(v, tuple) else v)
                    for k, v in cols.items()}
            n -= done
            total += done
        plain = {k: (materialize_strings(v) if isinstance(v, tuple) else v)
                 for k, v in cols.items()}
        handler.send_columns(plain, timestamps=ts, count=n)
        total += n
    return total
