"""The latency samples' 95th percentile (see end_to_end/latency_p50_ms). A
per-layer reading, not a bounded one: single stalls of the shared host move
it by 5-15 % from run to run of one tree (PERF.md, PR 22), which no bound
the contract allows can hold."""
import metrics


def read(run: dict):
    ms = run["latency_ms"]
    return metrics.percentile(ms, 95) if ms.size else None
