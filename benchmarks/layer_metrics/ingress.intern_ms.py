"""Ingress pipeline: mean dictionary-interning wall per worker run over the
window. Source: `stage_ms.intern`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "intern")
