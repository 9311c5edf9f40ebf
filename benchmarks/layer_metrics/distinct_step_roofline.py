"""Kernels: the distinct step's share of its roofline — the least time the
chip could take for the step's bytes (roofline_distinct.py, peaks/) over the
step's device time per execution from the trace's `XLA Modules` line. The
program gives a single-stream query's step no name of its own (`jit_step(
<fingerprint>)`); the deployment has one query, so the step is the costliest
`jit_step` program of the slice, as in `agg_step_roofline`. A configuration
without a window capacity of its own leaves nothing to read."""
import roofline
import roofline_distinct


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # a roofline share is a statement about the chip
    sizes = run["config"]["sizes"]
    modules = (run.get("reduced_trace") or {}).get("module_seconds") or {}
    steps = [v for k, v in modules.items() if k.startswith("jit_step(")]
    if not steps or "expire" not in sizes:
        return None
    seconds, count = max(steps)
    least = roofline.least_seconds(
        roofline_distinct.distinct_step(sizes["batch"]),
        run["device"]["kind"])
    return 100.0 * least["seconds"] / (seconds / count)
