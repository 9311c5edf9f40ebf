"""Read-back + callbacks, and everything between accept and callback:
median time from a frame's `200` to the block that holds its newest event
reaching the callback. Source: producers' and callback's logs."""
import numpy as np

import metrics


def read(run: dict):
    ms = metrics.block_latency_ms(run["frames"], run["delivered"],
                                  run["events"].stride, run["t0_ns"], run["t_end_ns"],
                                  since="done_ns")
    return float(np.median(ms)) if ms.size else None
