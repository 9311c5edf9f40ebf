"""Junction and dispatch: writes a table on the device index dropped in the
window because their key found no free row (`@capacity(rows=...)` used up,
or both of the key's buckets full): the deployment's guarantee is 0.
Source: the device counter as `statistics_report()["tables"][<table>]
["dropped"]` shows it, synced at each report and every 64th write, as a
delta. A program without that section leaves nothing to read."""


def read(run: dict):
    t0, t1 = run["stats0"].get("tables"), run["stats1"].get("tables")
    if not t0 or not t1:
        return None
    return float(sum(z["dropped"] - t0[n]["dropped"]
                     for n, z in t1.items() if n in t0))
