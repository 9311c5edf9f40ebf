"""`trades`: upstream's performance-sample stream, seeded:
`(symbol string, price float|double, volume long[, timestamp long])`.

Symbols uniform over `keys` distinct values `S0000000`.., or over the
literal list `symbols`; price a uniform integer in [price_lo, price_hi)
times `price_step` (multiples of 0.25 keep float32 sums exact, so the
reference comparison can be exact too; with 1..3999 x 0.25 about 70 % pass
`price < 700`); volume uniform in [volume_lo, volume_hi). `attributes` names
the stream's attributes in order (default symbol, price, volume); those in
`event_index_attributes` are creation stamps the producer writes at send:
each event's global index, like the frame's own timestamps. A frame is a
function of (seed, stream, producer, slot) and nothing else, so the parent
regenerates for its checks exactly what a producer process put on the wire.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

import sxf1

SYMBOL_WIDTH = 8  # 'S' + 7 digits
ATTRIBUTES = ("symbol", "price", "volume")


def _rng(seed: int, stream: str, producer: int, slot: int):
    return np.random.default_rng(
        [seed, zlib.crc32(stream.encode()), producer, slot])


def columns(params: dict, seed: int, stream: str, producer: int,
            slot: int) -> dict:
    """The frame's events as plain columns: symbol ids, prices, volumes."""
    rng = _rng(seed, stream, producer, slot)
    n = params["rows_per_frame"]
    keys = len(params["symbols"]) if "symbols" in params else params["keys"]
    return {
        "symbol": rng.integers(0, keys, n),
        "price": rng.integers(params["price_lo"], params["price_hi"], n)
        * params["price_step"],
        "volume": rng.integers(params["volume_lo"], params["volume_hi"], n),
    }


def symbol_bytes(ids: np.ndarray) -> np.ndarray:
    """uint8 [n, 8]: 'S' and seven decimal digits, vectorised."""
    out = np.empty((ids.size, SYMBOL_WIDTH), np.uint8)
    out[:, 0] = ord("S")
    rest = ids.astype(np.int64)
    for pos in range(SYMBOL_WIDTH - 1, 0, -1):
        out[:, pos] = rest % 10 + ord("0")
        rest = rest // 10
    return out


@functools.lru_cache(maxsize=2)
def _symbol_table(n: int) -> np.ndarray:
    table = np.empty(n, object)
    table[:] = [f"S{i:07d}" for i in range(n)]
    return table


def symbol_strings(ids, params=None) -> list:
    """The symbols as Python strings; many ids go through a table of all
    symbols up to the largest, built once (the checks decode 64 frames)."""
    ids = np.asarray(ids, np.int64)
    if params and "symbols" in params:
        return np.array(params["symbols"], object)[ids].tolist()
    if ids.size < 4096:
        return [f"S{i:07d}" for i in ids.tolist()]
    size = 1 << int(ids.max()).bit_length()
    return _symbol_table(size)[ids].tolist()


def wire_columns(cols: dict, typecodes, params=None) -> list:
    """`sxf1.encode_frame` input, in the stream's attribute order, with a
    per-frame dictionary made on the producer's side. `typecodes` are the
    wire codes of the deployed stream's attributes (the parent reads them
    from the runtime: a `double` travels as the device's float32)."""
    params = params or {}
    uniq, inverse = np.unique(cols["symbol"], return_inverse=True)
    values = [params["symbols"][i] for i in uniq.tolist()] \
        if "symbols" in params else symbol_bytes(uniq)
    stamps = params.get("event_index_attributes", ())
    out = []
    for name, code in zip(params.get("attributes", ATTRIBUTES), typecodes):
        if code == "s":
            out.append(("s", (values, inverse.astype(np.int32))))
        elif name in stamps:
            out.append((code, sxf1.EVENT_INDEX))
        else:
            out.append((code, cols[name]))
    return out
