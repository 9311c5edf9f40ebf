"""Service front: of the wire frames accepted in the window, the share whose
string dictionaries the extension decoded (`native/columnar.c` `decode_dict`)
and not the Python loop: 100 wherever the extension is loaded. Source: the
counters `wire_native_frames` over `frames_in`, as deltas; nothing to read
from a program that has no such counter."""
import layers


def read(run: dict):
    native = frames = 0
    for a, z in zip(layers.pipelines(run["stats0"], run),
                    layers.pipelines(run["stats1"], run)):
        if "wire_native_frames" not in a or "wire_native_frames" not in z:
            return None
        native += z["wire_native_frames"] - a["wire_native_frames"]
        frames += z["frames_in"] - a["frames_in"]
    return 100.0 * native / frames if frames > 0 else None
