"""Ingress pipeline: the feeder's CPU time inside `ingress.h2d_ms`; the rest of
that wall is the wait for the interpreter lock. Source: the span
`siddhi.feeder.h2d`, cell `stage_ms.h2d.cpu_ms`, as a delta."""
import spans


def read(run: dict):
    return spans.stage_cpu_mean_ms(run, "h2d")
