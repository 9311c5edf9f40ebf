"""Junction and dispatch: partial matches the pattern steps dropped in the
window (the pending table full): the deployment's guarantee is 0. Source:
the device counter as `statistics_report()["patterns"][<query>]
["pending_dropped"]` shows it, synced at each report, as a delta. A program
without that section leaves nothing to read (the account reads `overflow`
at the run's end either way)."""


def read(run: dict):
    p0, p1 = run["stats0"].get("patterns"), run["stats1"].get("patterns")
    if not p0 or not p1:
        return None
    return float(sum(z["pending_dropped"] - p0[name]["pending_dropped"]
                     for name, z in p1.items() if name in p0))
