"""Kernels: the table write's share of its roofline — the least time the
chip could take for the bytes of one frame of registrations
(roofline_table.py, peaks/) over the device time per execution of the
table-write program (`table.write_ms`). Nothing to read without that
program, or in a cell whose change stream is not its first input."""
import registry
import roofline
import roofline_table


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # a roofline share is a statement about the chip
    seconds = registry.load_module(
        "layer_metrics", "table.write_ms").per_execution_s(run)
    if seconds is None:
        return None
    rows = run["events"].plans[0]["rows"]
    least = roofline.least_seconds(roofline_table.table_write(rows),
                                   run["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
