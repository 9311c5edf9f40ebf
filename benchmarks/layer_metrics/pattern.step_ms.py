"""Device step: device time per execution of the pattern's step programs,
both fed streams, told from every other program by their names on the
trace's `XLA Modules` line (`jit_pattern_step_<stream>`; the program names
them since PR 30). A program without those names (a parent commit: every
step is `jit_step`) leaves nothing to read."""

PROGRAMS = "jit_pattern_step_"


def executions(run: dict) -> dict:
    """stream -> (device seconds, executions) of its step program in the
    traced slice."""
    modules = (run.get("reduced_trace") or {}).get("module_seconds") or {}
    out: dict = {}
    for name, (seconds, count) in modules.items():
        if name.startswith(PROGRAMS):
            stream = name[len(PROGRAMS):].split("(")[0]
            have = out.get(stream, (0.0, 0))
            out[stream] = (have[0] + seconds, have[1] + count)
    return out


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # device time is a statement about the chip
    mine = executions(run).values()
    runs = sum(count for _, count in mine)
    return 1e3 * sum(seconds for seconds, _ in mine) / runs if runs else None
