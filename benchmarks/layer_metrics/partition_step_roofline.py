"""Kernels: the partition's keyed step's share of its roofline — the least
time the chip could take for the step's bytes (roofline_partition.py,
peaks/) over the step's device time per execution from the trace's `XLA
Modules` line. The keyed step stays a `jit_step` of the query family (the
inner query's own step, with a key axis in its state); the deployment has
one query, so the step is the costliest `jit_step` program of the slice, as
in `agg_step_roofline`. A program without a `partitions` section in its
statistics (none of its partitions on the keyed step) leaves nothing to
read."""
import roofline
import roofline_partition


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # a roofline share is a statement about the chip
    partitions = run["stats1"].get("partitions") or {}
    modules = (run.get("reduced_trace") or {}).get("module_seconds") or {}
    steps = [v for k, v in modules.items() if k.startswith("jit_step(")]
    if not steps or not partitions:
        return None
    seconds, count = max(steps)
    length = max(p["length"] for p in partitions.values())
    least = roofline.least_seconds(
        roofline_partition.partition_step(run["config"]["sizes"]["batch"],
                                          length),
        run["device"]["kind"])
    return 100.0 * least["seconds"] / (seconds / count)
