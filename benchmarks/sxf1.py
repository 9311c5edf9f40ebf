"""The benchmark's own SXF1 encoder (the wire format of siddhi_tpu/io/wire.py),
vectorised, and the in-place event-index patch the producers use.

Kept here, not imported from the program, for two reasons: producers must not
import `siddhi_tpu` (the parent holds the chip), and the program's encoder
walks a string column row by row in Python, which at 131,072 rows and
~120,000 distinct symbols per frame would make the frame pool the longest
part of set-up. A test decodes these frames with the program's decoder.

    body    := u32 payload_len | payload
    payload := 'SXF1' | u8 flags | u16 n_cols | u32 n_rows
               | i64 ts[n_rows]                     (flags bit0)
               | col*
    col     := u8 typecode | raw values             (b i l f d)
             | u8 's' | u32 dict_n | dict_n * (u16 len | utf8) | i32 idx[n_rows]
"""

from __future__ import annotations

import struct

import numpy as np

MAGIC = b"SXF1"
FLAG_HAS_TS = 0x01
ROWS_OFFSET = 4 + 4 + 1 + 2  # length prefix, magic, flags, n_cols
TS_OFFSET = ROWS_OFFSET + 4
_RAW = {"b": "u1", "i": "<i4", "l": "<i8", "f": "<f4", "d": "<f8"}
#: data of a numeric column that carries each event's global index (a
#: creation stamp the producer writes at send, as `patch_timestamps` does
#: for the frame's own timestamp block)
EVENT_INDEX = "event-index"


class Frame(bytearray):
    """A frame body that knows where its event-index columns lie:
    `index_blocks` is a tuple of (byte offset, numpy dtype)."""

    index_blocks: tuple = ()


def dictionary_bytes(values) -> bytes:
    """The `dict_n * (u16 len | utf8)` block. `values` is either a uint8
    matrix [dict_n, width] of fixed-width strings (vectorised) or a sequence
    of str."""
    if isinstance(values, np.ndarray) and values.dtype == np.uint8:
        n, width = values.shape
        rec = np.empty((n, 2 + width), np.uint8)
        rec[:, 0] = width & 0xFF
        rec[:, 1] = width >> 8
        rec[:, 2:] = values
        return rec.tobytes()
    out = []
    for v in values:
        raw = v.encode("utf-8")
        out.append(struct.pack("<H", len(raw)))
        out.append(raw)
    return b"".join(out)


def encode_frame(columns, n: int) -> Frame:
    """One frame with a zeroed timestamp block (see `patch_timestamps`).
    `columns` is a list, in the stream's attribute order, of
    (typecode, data): raw numeric arrays for b/i/l/f/d (or `EVENT_INDEX`,
    zeroed here and patched with the timestamps), and for 's' a pair
    (dictionary values as `dictionary_bytes` takes them, int32 indexes)."""
    parts = [MAGIC, struct.pack("<BHI", FLAG_HAS_TS, len(columns), n),
             bytes(8 * n)]
    index_blocks = []
    for code, data in columns:
        if code == "s":
            values, idx = data
            parts.append(struct.pack("<BI", ord("s"), len(values)))
            parts.append(dictionary_bytes(values))
            parts.append(np.ascontiguousarray(idx[:n], "<i4").tobytes())
            continue
        parts.append(code.encode())
        if isinstance(data, str) and data == EVENT_INDEX:
            index_blocks.append((4 + sum(len(p) for p in parts), _RAW[code]))
            data = np.zeros(n, _RAW[code])
        parts.append(np.ascontiguousarray(data[:n], _RAW[code]).tobytes())
    payload = b"".join(parts)
    frame = Frame(struct.pack("<I", len(payload)) + payload)
    frame.index_blocks = tuple(index_blocks)
    return frame


def frame_rows(frame) -> int:
    return struct.unpack_from("<I", frame, ROWS_OFFSET)[0]


def patch_timestamps(frame: bytearray, first: int) -> None:
    """Rewrite the frame's timestamp block, and every event-index column,
    in place to first, first+1, ...: every event's timestamp is its global
    index."""
    n = frame_rows(frame)
    index = np.arange(first, first + n, dtype=np.int64)
    for offset, dtype in ((TS_OFFSET, "<i8"),
                          *getattr(frame, "index_blocks", ())):
        np.frombuffer(frame, dtype, n, offset)[:] = index
