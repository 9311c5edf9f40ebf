"""Ingress pipeline: mean wait of a handler for the submit lock and a free run
of the ring, per run claimed: back-pressure. Source: the span
`siddhi.ingress.claim_wait`, cell `stage_ms.claim_wait`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "claim_wait")
