"""Read-back + callbacks: mean wall of a batch's `device_get`: the wait for the
step to end, the D2H copy and the unpack. Source: the span
`siddhi.readback.fetch`, cell `readback.stage_ms.fetch`, as a delta."""
import spans


def read(run: dict):
    return spans.readback_mean_ms(run, "fetch")
