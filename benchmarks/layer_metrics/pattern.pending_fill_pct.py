"""State: the high water of live partial matches in the window over the
pending table's capacity, over all pattern queries. Source:
`statistics_report()["patterns"][<query>]`: `live_hwm` is the high water
since the report before (it starts anew at each), so the window's is the
largest of the reports taken after its start — its end's and, in a traced
run, the slice's two; `pending_capacity` is the table's. A program without
that section leaves nothing to read."""


def read(run: dict):
    trace = run.get("trace") or {}
    reports = [s.get("patterns") for s in (
        trace.get("stats_open"), trace.get("stats_close"), run["stats1"])
        if s]
    if not run["stats0"].get("patterns") or not all(reports):
        return None
    fill = [100.0 * max(r[name]["live_hwm"] for r in reports)
            / z["pending_capacity"]
            for name, z in run["stats1"]["patterns"].items()]
    return max(fill) if fill else None
