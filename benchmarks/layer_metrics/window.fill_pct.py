"""State: the high water of live rows in a sliding window's ring over its
capacity, over all windowed queries. Source:
`statistics_report()["windows"][<query>]`: `live_hwm` is the high water
since the report before (it starts anew at each), so the window's is the
largest of the reports taken after its start — its end's and, in a traced
run, the slice's two; `capacity` is the ring's rows. A program without that
section leaves nothing to read."""


def read(run: dict):
    trace = run.get("trace") or {}
    reports = [s.get("windows") for s in (
        trace.get("stats_open"), trace.get("stats_close"), run["stats1"])
        if s]
    if not run["stats0"].get("windows") or not all(reports):
        return None
    fill = [100.0 * max(r[name]["live_hwm"] for r in reports)
            / z["capacity"]
            for name, z in run["stats1"]["windows"].items()]
    return max(fill) if fill else None
