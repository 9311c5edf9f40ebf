"""`temps`: the SiddhiQL guide's `TempStream (deviceID long, roomNo int,
temp double[, timestamp long])`, seeded, for a fleet of `keys` devices.

A device is a rank in `[0, keys)`, drawn uniformly per event. What the
stream carries of it is its serial number, `deviceID`: a seeded bijection of
the rank into 63 bits (two rounds of odd multiply and xor-shift modulo 2^63,
each invertible), so that no arithmetic on the id gives a slot — a table
indexed by the id itself is not this deployment — and two devices never
share an id. A device stands in one room: `roomNo` is its rank mixed, modulo
`rooms`. `temp` is uniform on a grid of 1/64 between `temp_lo` and `temp_hi`
degrees: exact in float32, so a `max` over it is a selection and compares
bit for bit under the engine's DOUBLE -> float32 default. Attributes named
in `event_index_attributes` are creation stamps the producer writes at send
(each event's global index), as in `generators/trades.py`.

A frame is a function of (seed, stream, producer, slot) and nothing else;
the columns keep the rank beside the id (`device`) for the reference, which
keeps its per-device state by rank.
"""

from __future__ import annotations

import zlib

import numpy as np

import sxf1

ATTRIBUTES = ("deviceID", "roomNo", "temp")
_MASK = np.uint64((1 << 63) - 1)
_ODD_A = np.uint64(0x9E3779B97F4A7C15)
_ODD_B = np.uint64(0xD6E8FEB86659FD93)
GRID = 64.0


def _rng(seed: int, stream: str, producer: int, slot: int):
    return np.random.default_rng(
        [seed, zlib.crc32(stream.encode()), producer, slot])


def device_ids(ranks, seed: int) -> np.ndarray:
    """The serial numbers of the devices at `ranks`: a bijection of
    [0, 2^63) onto itself, keyed by the seed."""
    offset = np.uint64(np.random.default_rng(
        [seed, zlib.crc32(b"deviceID")]).integers(0, 1 << 62))
    x = (np.asarray(ranks).astype(np.uint64) * _ODD_A + offset) & _MASK
    x ^= x >> np.uint64(31)
    x = (x * _ODD_B) & _MASK
    x ^= x >> np.uint64(29)
    return x.astype(np.int64)


def rooms_of(ranks, rooms: int) -> np.ndarray:
    x = np.asarray(ranks).astype(np.uint64) * _ODD_B
    x ^= x >> np.uint64(33)
    return (x % np.uint64(rooms)).astype(np.int32)


def columns(params: dict, seed: int, stream: str, producer: int,
            slot: int) -> dict:
    """The frame's events as plain columns: device ranks, their ids and
    rooms, temperatures."""
    rng = _rng(seed, stream, producer, slot)
    n = params["rows_per_frame"]
    device = rng.integers(0, params["keys"], n)
    lo, hi = (int(round(params[k] * GRID)) for k in ("temp_lo", "temp_hi"))
    return {
        "device": device,
        "deviceID": device_ids(device, seed),
        "roomNo": rooms_of(device, params["rooms"]),
        "temp": rng.integers(lo, hi + 1, n) / GRID,
    }


def wire_columns(cols: dict, typecodes, params=None) -> list:
    """`sxf1.encode_frame` input, in the stream's attribute order.
    `typecodes` are the wire codes of the deployed stream's attributes (the
    parent reads them from the runtime: a `double` travels as the device's
    float32)."""
    params = params or {}
    stamps = params.get("event_index_attributes", ())
    return [(code, sxf1.EVENT_INDEX if name in stamps else cols[name])
            for name, code in zip(params.get("attributes", ATTRIBUTES),
                                  typecodes)]
