"""Load generator: how late frames left against their schedule, 95th
percentile. Lateness is inside the latency (counted from the due time); a
starved generator must not read as a slow or a fast server. A closed loop
has no schedule to be late against."""
import metrics


def read(run: dict):
    if run["mode"] != "paced":
        return None
    ms = metrics.lateness_ms(run["frames"])
    return metrics.percentile(ms, 95) if ms.size else None
