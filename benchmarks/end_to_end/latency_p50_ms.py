"""Median of the latency samples: one per block delivered to the callback
inside the window, delivery time minus the due time of the frame that holds
the block's newest input event (`metrics.block_latency_ms`)."""
import metrics


def read(run: dict):
    ms = run["latency_ms"]
    return metrics.percentile(ms, 50) if ms.size else None
