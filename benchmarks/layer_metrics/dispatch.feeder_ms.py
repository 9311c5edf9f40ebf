"""Junction + dispatch: the feeder's wall inside dispatch per batch. The
program calls it `stage_ms.device`; it is host wall around the dispatch
calls, NOT device time (that is `device.step_ms`, from the trace)."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "device")
