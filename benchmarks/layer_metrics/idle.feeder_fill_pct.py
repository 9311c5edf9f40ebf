"""Device step: of the traced slice's device idle time (gaps of 0.1 ms and
more), the share during which the feeder was waiting for the workers to publish rows.
Source: `siddhi.feeder.fill` events beside the device's, one trace."""
import spans


def read(run: dict):
    return spans.idle_share_pct(run, "fill")
