"""Ingress pipeline: a worker's CPU time inside `ingress.intern_ms`; the rest
of that wall is the wait for the controller lock
(`ingress.intern_lock_wait_ms`) and for the interpreter. Source: the span
`siddhi.ingress.intern`, cell `stage_ms.intern.cpu_ms`, as a delta."""
import spans


def read(run: dict):
    return spans.stage_cpu_mean_ms(run, "intern")
