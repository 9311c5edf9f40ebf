"""Service front: median POST round trip as the producers saw it (send to
`200`), over the frames sent in the window. Source: producers' logs."""
import numpy as np

import metrics


def read(run: dict):
    ms = metrics.post_ms(run["frames"])
    return float(np.median(ms)) if ms.size else None
