"""Device step: device time per execution of the join's two probe programs,
told from every other program by their names on the trace's `XLA Modules`
line (`jit_join_probe_left`, `jit_join_probe_right`; the program names them
since PR 26). A program without those names (a parent commit: every step is
`jit_step`) leaves nothing to read."""

PROGRAMS = ("jit_join_probe_left(", "jit_join_probe_right(")


def per_execution_s(run: dict):
    """Seconds per execution over both probe programs; None without them."""
    modules = (run.get("reduced_trace") or {}).get("module_seconds") or {}
    mine = [v for k, v in modules.items() if k.startswith(PROGRAMS)]
    runs = sum(count for _, count in mine)
    return sum(seconds for seconds, _ in mine) / runs if runs else None


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # device time is a statement about the chip
    seconds = per_execution_s(run)
    return None if seconds is None else seconds * 1e3
