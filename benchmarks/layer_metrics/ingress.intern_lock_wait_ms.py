"""Ingress pipeline: mean wait of a worker for the controller lock before it
interns, per acquire; it is inside `ingress.intern_ms`. Source: the span
`siddhi.ingress.intern_lock_wait`, cell of that name, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "intern_lock_wait")
