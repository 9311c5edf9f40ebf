"""Junction + dispatch: the part of `dispatch.feeder_ms` under the controller
lock: every step's dispatch and the read-back's submit. Source: the span
`siddhi.feeder.dispatch`, cell `stage_ms.dispatch`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "dispatch")
