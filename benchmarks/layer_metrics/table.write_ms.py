"""Device step: device time per execution of the table-write program, told
from every other program by its name on the trace's `XLA Modules` line
(`jit_table_write_<table>`, since PR 44: one a batch of a query whose output
is a table on the device index). A program without that name leaves nothing
to read."""

PREFIX = "jit_table_write_"


def per_execution_s(run: dict):
    """Seconds per execution over the table-write programs; None without
    them."""
    modules = (run.get("reduced_trace") or {}).get("module_seconds") or {}
    mine = [v for k, v in modules.items() if k.startswith(PREFIX)]
    runs = sum(count for _, count in mine)
    return sum(seconds for seconds, _ in mine) / runs if runs else None


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # device time is a statement about the chip
    seconds = per_execution_s(run)
    return None if seconds is None else seconds * 1e3
