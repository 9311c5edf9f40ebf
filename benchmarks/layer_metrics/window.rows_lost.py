"""State: rows a sliding window lost in the measured window — live rows its
ring overwrote (`ring_overflow`) plus due rows it let go late because more
were due in one step than its expiry width (`expiry_deferred`): the
deployment's guarantee is 0. Source: the device counters as
`statistics_report()["windows"][<query>]` shows them, synced at each report,
as a delta. A program without that section leaves nothing to read (the
account reads `overflow` at the run's end either way)."""

COUNTERS = ("ring_overflow", "expiry_deferred")


def read(run: dict):
    w0, w1 = run["stats0"].get("windows"), run["stats1"].get("windows")
    if not w0 or not w1:
        return None
    return float(sum(z[c] - w0[name][c] for name, z in w1.items()
                     if name in w0 for c in COUNTERS))
