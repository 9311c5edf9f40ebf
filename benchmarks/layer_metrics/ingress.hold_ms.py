"""Ingress pipeline: mean residence of a built batch in the feeder's double
buffer, from its upload's start to its delivery's: it waits for the next
batch to fill. Source: cell `stage_ms.hold`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "hold")
