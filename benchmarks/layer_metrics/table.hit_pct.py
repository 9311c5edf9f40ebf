"""Device step: of the probes a join made of a table's device index in the
window, the share that found a row (every registered device has one, so
about 100 where all are registered). Source: `statistics_report()["tables"]
[<table>]` `hits` over `probes`, as deltas. A program without that section,
or a window with no probe, leaves nothing to read."""


def read(run: dict):
    t0, t1 = run["stats0"].get("tables"), run["stats1"].get("tables")
    if not t0 or not t1:
        return None
    probes = sum(z["probes"] - t0[n]["probes"] for n, z in t1.items()
                 if n in t0)
    hits = sum(z["hits"] - t0[n]["hits"] for n, z in t1.items() if n in t0)
    return 100.0 * hits / probes if probes > 0 else None
