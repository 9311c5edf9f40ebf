"""Ingress pipeline: mean time per batch the feeder waited for the workers to
publish the batch's rows: the feeder starved. Source: the span
`siddhi.feeder.fill`, cell `stage_ms.fill`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "fill")
