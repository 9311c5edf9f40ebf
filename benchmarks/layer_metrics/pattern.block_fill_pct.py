"""Read-back and callbacks: of the out-block lanes the pattern steps put out
in the window, the share that held a row. A quote step's out block is as
wide as the pending table whatever it holds (a trade step's is one empty
lane), and the read-back fetches the whole capacity and compacts on the
host. Source: the rows of the blocks that reached the callback in the
window, over the program's `out_lanes` counter
(`statistics_report()["patterns"]`, cumulative over steps) as a delta. A
program without that section leaves nothing to read."""


def read(run: dict):
    p0, p1 = run["stats0"].get("patterns"), run["stats1"].get("patterns")
    if not p0 or not p1:
        return None
    lanes = sum(z["out_lanes"] - p0[name]["out_lanes"]
                for name, z in p1.items() if name in p0)
    if lanes <= 0:
        return None
    delivered = run["delivered"]
    t = delivered["enter_ns"]
    inside = (t >= run["t0_ns"]) & (t < run["t_end_ns"])
    return 100.0 * float(delivered["rows"][inside].sum()) / lanes
