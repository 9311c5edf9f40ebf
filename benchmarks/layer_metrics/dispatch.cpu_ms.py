"""Junction and dispatch: the feeder's CPU time inside `dispatch.work_ms`
(the body under the controller lock); the rest of that wall is the thread not
running: the wait for room in the device's queue, for the interpreter, for
the read-back's bounded queue. Source: the span `siddhi.feeder.dispatch`,
cell `stage_ms.dispatch.cpu_ms`, as a delta."""
import spans


def read(run: dict):
    return spans.stage_cpu_mean_ms(run, "dispatch")
