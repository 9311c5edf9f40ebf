"""Ingress pipeline: mean wait of a worker for its turn to intern, per run; it
is inside `native.decode_ms`. Source: the span `siddhi.ingress.ticket_wait`,
cell `stage_ms.ticket_wait`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "ticket_wait")
