"""Ingress pipeline: of the batches the feeders delivered in the window, the
share that left the double buffer because the ring ran empty behind them
(`core/ingress.py` `_feed_loop`: a built batch is kept only while rows for
the next one are waiting) and not behind the next batch's upload or at a
flush: near 100 where frames arrive further apart than the feeder's work on
one, near 0 where the ring always has rows. Source: the counters
`batches_delivered_on_starve` over `batches_delivered`, as deltas, summed
over the input streams; nothing to read from a program that has no such
counter."""
import layers


def read(run: dict):
    starved = delivered = 0
    for a, z in zip(layers.pipelines(run["stats0"], run),
                    layers.pipelines(run["stats1"], run)):
        if "batches_delivered_on_starve" not in a \
                or "batches_delivered_on_starve" not in z:
            return None
        starved += z["batches_delivered_on_starve"] \
            - a["batches_delivered_on_starve"]
        delivered += z["batches_delivered"] - a["batches_delivered"]
    return 100.0 * starved / delivered if delivered > 0 else None
