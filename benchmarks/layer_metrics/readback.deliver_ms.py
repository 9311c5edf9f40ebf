"""Read-back + callbacks: mean time from a batch's fetch to its callback's
return: the reorder wait and the callback (span `siddhi.readback.callback`).
Source: cell `readback.stage_ms.deliver`, as a delta."""
import spans


def read(run: dict):
    return spans.readback_mean_ms(run, "deliver")
