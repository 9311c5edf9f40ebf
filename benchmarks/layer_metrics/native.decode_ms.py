"""Native host path: mean wire-decode wall per worker run over the window.
Source: the program's cumulative `stage_ms.decode`, as a delta."""
import layers


def read(run: dict):
    return layers.stage_mean_ms(run, "decode")
