"""Service front: mean wall of one frame's wire decode (`wire.decode_frame`, a
Python `str` per dictionary entry) in the HTTP handler's thread. Source: the
span `siddhi.front.wire`, cell `stage_ms.wire`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "wire")
