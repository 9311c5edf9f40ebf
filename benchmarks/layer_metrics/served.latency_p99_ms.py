"""The latency samples' 99th percentile. A per-layer reading until a cell
delivers enough blocks to hold it steady (ten samples beyond p99 needs
1,000 blocks a window)."""
import metrics


def read(run: dict):
    ms = run["latency_ms"]
    return metrics.percentile(ms, 99) if ms.size else None
