"""Junction and dispatch: mean ms a step that the fetch of the keyed step's
counters (keys held, events dropped) takes under the controller lock: a
`device_get` every 64th step, which waits for every step dispatched so far,
and the dispatch path's only device-to-host fetch. What
`dispatch.drop_sync_ms` is for the join, the pattern and the time window,
whose sections it reads; this one reads the cell `stage_ms.drop_sync` of
`statistics_report()["partitions"][<partition>]` (span
`siddhi.partition.drop_sync`) over that section's `steps`, both as deltas.
A program without that section leaves nothing to read."""


def read(run: dict):
    ms = steps = 0.0
    before = run["stats0"].get("partitions") or {}
    for name, z in (run["stats1"].get("partitions") or {}).items():
        a = before.get(name)
        if a is None:
            continue
        ms += z["stage_ms"]["drop_sync"]["total_ms"] \
            - a["stage_ms"]["drop_sync"]["total_ms"]
        steps += z["steps"] - a["steps"]
    return ms / steps if steps > 0 else None
