"""Service front: the handler thread's CPU time inside one frame's wire decode;
`front.wire_ms` minus this is the wait for the interpreter lock. Source: the
span `siddhi.front.wire`, cell `stage_ms.wire.cpu_ms`, as a delta."""
import spans


def read(run: dict):
    return spans.stage_cpu_mean_ms(run, "wire")
