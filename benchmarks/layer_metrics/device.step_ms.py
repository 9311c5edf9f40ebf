"""Device step: device time per dispatched batch — the sum of device-op
durations inside the traced slice over the batches the feeder delivered in
it. Source: the profiler trace, reduced by trace_reduce.py."""
import layers


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # device time is a statement about the chip
    reduced = run.get("reduced_trace") or {}
    batches = layers.slice_batches(run)
    if not batches or not reduced.get("op_total_s"):
        return None
    return reduced["op_total_s"] * 1e3 / batches
