"""Read-back and callbacks: of the out-block lanes the read-back fetched in
the window, the share that held a row. The keyed step's out block is the
batch, one lane an input event, so a frame of 131,072 events that all pass
fills it: 100. Source: the rows of the blocks that reached the callback in
the window, over as many blocks times the lanes per step from the program's
counters (`statistics_report()["partitions"]`: `out_lanes` over `steps`, as
deltas). A program without that section leaves nothing to read."""


def read(run: dict):
    lanes = steps = 0
    for name, z in (run["stats1"].get("partitions") or {}).items():
        a = (run["stats0"].get("partitions") or {}).get(name)
        if a is None:
            return None
        lanes += z["out_lanes"] - a["out_lanes"]
        steps += z["steps"] - a["steps"]
    if steps <= 0:
        return None
    delivered = run["delivered"]
    t = delivered["enter_ns"]
    inside = (t >= run["t0_ns"]) & (t < run["t_end_ns"])
    blocks = int(inside.sum())
    if not blocks:
        return None
    return 100.0 * float(delivered["rows"][inside].sum()) \
        / (blocks * lanes / steps)
