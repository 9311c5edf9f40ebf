"""Ingress pipeline: mean wall the feeder spends starting a batch's upload
(`EventBatch.from_numpy`). Source: `stage_ms.h2d`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "h2d")
