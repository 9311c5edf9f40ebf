"""Junction and dispatch: events the keyed step dropped in the window
because their key found no slot (the partition's `@capacity(keys=...)` used
up, or both of the key's table buckets full): the deployment's guarantee is
0. Source: the device counter as `statistics_report()["partitions"]
[<partition>]["keys_dropped"]` shows it, synced at each report and every
64th step, as a delta. A program without that section leaves nothing to
read (the account reads the counter at the run's end either way)."""


def read(run: dict):
    p0, p1 = run["stats0"].get("partitions"), run["stats1"].get("partitions")
    if not p0 or not p1:
        return None
    return float(sum(z["keys_dropped"] - p0[name]["keys_dropped"]
                     for name, z in p1.items() if name in p0))
