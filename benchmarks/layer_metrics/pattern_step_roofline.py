"""Kernels: the pattern steps' share of their roofline — the least time the
chip could take for the bytes of the trade and quote steps the slice ran
(roofline_pattern.py, peaks/) over the device time of those executions
(`pattern.step_ms`'s programs). The first input stream feeds the trade
step, the second the quote step."""
import registry
import roofline
import roofline_pattern


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None  # a roofline share is a statement about the chip
    ran = registry.load_module("layer_metrics",
                               "pattern.step_ms").executions(run)
    config = run["config"]
    streams = list(config["inputs"])
    if len(streams) != 2 or "pending" not in config["sizes"] \
            or not all(ran.get(s, (0, 0))[1] for s in streams):
        return None
    batch = config["sizes"]["batch"]
    work = zip(streams, (roofline_pattern.trade_step(batch),
                         roofline_pattern.quote_step(batch)))
    least = sum(ran[s][1] * roofline.least_seconds(
        w, run["device"]["kind"])["seconds"] for s, w in work)
    return 100.0 * least / sum(ran[s][0] for s in streams)
