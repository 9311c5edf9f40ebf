"""Kernels: the table join's share of its roofline — the least time the
chip could take for the bytes of one frame of readings probing the table's
index (roofline_table.py, peaks/) over the device time per execution of the
join's probe programs (`join.step_ms`). The share of readings that give a
row is the run's own: rows delivered over readings probed in the window.
Nothing to read without a table on the device index (no `tables` section
in the statistics)."""
import registry
import roofline
import roofline_table


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # a roofline share is a statement about the chip
    t0, t1 = run["stats0"].get("tables"), run["stats1"].get("tables")
    seconds = registry.load_module(
        "layer_metrics", "join.step_ms").per_execution_s(run)
    if not t0 or not t1 or seconds is None:
        return None
    probes = sum(z["probes"] - t0[n]["probes"] for n, z in t1.items()
                 if n in t0)
    delivered = run["delivered"]
    t = delivered["enter_ns"]
    inside = (t >= run["t0_ns"]) & (t < run["t_end_ns"])
    if probes <= 0:
        return None
    share = float(delivered["rows"][inside].sum()) / probes
    rows = run["events"].plans[-1]["rows"]
    least = roofline.least_seconds(roofline_table.table_join(rows, share),
                                   run["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
