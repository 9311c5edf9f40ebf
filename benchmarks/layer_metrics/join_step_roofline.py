"""Kernels: the join step's share of its roofline — the least time the chip
could take for the step's bytes (roofline_join.py, peaks/) over the device
time per execution of the two probe programs (`join.step_ms`)."""
import registry
import roofline
import roofline_join


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # a roofline share is a statement about the chip
    seconds = registry.load_module(
        "layer_metrics", "join.step_ms").per_execution_s(run)
    config = run["config"]
    keys = [data["params"].get("keys")
            for data in config["inputs"].values()]
    if seconds is None or "window" not in config["sizes"] or not all(keys):
        return None
    least = roofline.least_seconds(
        roofline_join.join_step(config["sizes"]["batch"],
                                config["sizes"]["window"], max(keys)),
        run["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
