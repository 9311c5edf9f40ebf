"""`registrations`: the change stream of a device registry,
`DeviceStream (deviceID long, roomNo int, type string, timestamp long)`,
seeded, over the fleet of `generators/temps.py` (the same ids and rooms).

A device is a rank in `[0, keys)`. The parent's warm-up frames (the virtual
producer `warm_producer`, which the traffic mix names: one past its own
producers) register every device once, in rank order; rows past the last
rank, and every row of the producers' own frames, re-register a uniform
device. Each registration keeps the device's own `roomNo` and draws its
`type` uniformly from `types`, so an update changes a row's type with
probability 1 - 1/len(types). `timestamp` is a creation stamp the producer
writes at send (each event's global index), as in `generators/temps.py`.

A frame is a function of (seed, stream, producer, slot) and nothing else;
the columns keep the rank (`device`) and the type's index (`type_code`)
beside the wire's values, for the reference.
"""

from __future__ import annotations

import numpy as np

import registry
import sxf1

temps = registry.load_module("generators", "temps")

ATTRIBUTES = ("deviceID", "roomNo", "type")


def columns(params: dict, seed: int, stream: str, producer: int,
            slot: int) -> dict:
    rng = temps._rng(seed, stream, producer, slot)
    n = params["rows_per_frame"]
    keys = params["keys"]
    device = rng.integers(0, keys, n)
    if producer == params.get("warm_producer"):
        rank = slot * n + np.arange(n)
        device = np.where(rank < keys, rank, device)
    return {
        "device": device,
        "deviceID": temps.device_ids(device, seed),
        "roomNo": temps.rooms_of(device, params["rooms"]),
        "type_code": rng.integers(0, len(params["types"]), n).astype(
            np.int32),
    }


def wire_columns(cols: dict, typecodes, params=None) -> list:
    """`sxf1.encode_frame` input, in the stream's attribute order; the type
    travels through the frame's dictionary of the `types`."""
    params = params or {}
    stamps = params.get("event_index_attributes", ())
    out = []
    for name, code in zip(params.get("attributes", ATTRIBUTES), typecodes):
        if name in stamps:
            out.append((code, sxf1.EVENT_INDEX))
        elif code == "s":
            out.append(("s", (list(params["types"]), cols["type_code"])))
        else:
            out.append((code, cols[name]))
    return out
