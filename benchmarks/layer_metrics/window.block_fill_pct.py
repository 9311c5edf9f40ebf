"""Read-back and callbacks: of the out-block lanes the read-back fetched in
the window, the share that held a row. A sliding window's step emits a chunk
of batch + expiry-width lanes whatever it holds, the selector's out block is
as wide, and the read-back fetches the whole capacity and compacts on the
host. Source: the rows of the blocks that reached the callback in the
window, over as many blocks times the lanes per step from the program's
counters (`statistics_report()["windows"]`: `out_lanes` over `steps`, as
deltas). A program without that section leaves nothing to read."""


def read(run: dict):
    lanes = steps = 0
    for name, z in (run["stats1"].get("windows") or {}).items():
        a = (run["stats0"].get("windows") or {}).get(name)
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
