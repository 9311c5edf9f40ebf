"""Junction and dispatch: matched pairs the join steps dropped in the window
(beyond `join_max_matches` a probe, or beyond the out block): the
deployment's guarantee is 0. Source: the device counter as
`statistics_report()["joins"][<query>]["pairs_dropped"]` shows it, synced at
each report, as a delta. A program without that section leaves nothing to
read (the account reads `overflow` at the run's end either way)."""


def read(run: dict):
    joins0, joins1 = run["stats0"].get("joins"), run["stats1"].get("joins")
    if not joins0 or not joins1:
        return None
    return float(sum(z["pairs_dropped"] - joins0[name]["pairs_dropped"]
                     for name, z in joins1.items() if name in joins0))
