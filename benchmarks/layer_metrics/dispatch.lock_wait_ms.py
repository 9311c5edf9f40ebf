"""Junction + dispatch: the part of `dispatch.feeder_ms` the feeder waited for
the controller lock (the workers hold it while they intern). Source: the span
`siddhi.feeder.lock_wait`, cell `stage_ms.lock_wait`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "lock_wait")
