"""Kernels: the `agg` step's share of its roofline — the least time the
chip could take for the step's bytes (roofline.py, peaks/) over the
step's device time per execution from the trace's `XLA Modules` line. The
program gives its steps no name of their own (every query's step is
`jit_step(<fingerprint>)`), so the agg step is taken to be the costliest
`jit_step` program of the slice, which in a `groupby_1m` cell it is by
three orders of magnitude."""
import roofline


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # a roofline share is a statement about the chip
    modules = (run.get("reduced_trace") or {}).get("module_seconds") or {}
    steps = [v for k, v in modules.items() if k.startswith("jit_step(")]
    if not steps:
        return None
    seconds, count = max(steps)
    least = roofline.least_seconds(
        roofline.agg_step(run["config"]["sizes"]["batch"]),
        run["device"]["kind"])
    return 100.0 * least["seconds"] / (seconds / count)
