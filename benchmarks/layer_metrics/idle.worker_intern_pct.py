"""Device step: of the traced slice's device idle time (gaps of 0.1 ms and
more), the share during which at least one ingress worker was inside
`intern_column` holding the interpreter — what the feeder waits for while
its own span reads `siddhi.feeder.h2d` (`idle.feeder_h2d_pct`). Source: the
union of every worker's `siddhi.ingress.intern` events beside the device's,
one trace."""
import spans

INTERN = "siddhi.ingress.intern"
STATE = "worker_intern"  # no state of the feeder's: a place in the table


def read(run: dict):
    found = spans.host_spans(run)
    if not found or INTERN not in found:
        return None
    # spans.idle_share_pct's arithmetic, with the workers' events standing
    # where a feeder state's would
    return spans.idle_share_pct(
        {**run, "host_spans": {spans.FEEDER + STATE: found[INTERN]}}, STATE)
