"""Read-back + callbacks: mean wait of a submitted batch for a fetch worker.
Source: `statistics_report()["readback"]["stage_ms"]["queue"]` (stamped in
the span `siddhi.readback.submit`), as a delta."""
import spans


def read(run: dict):
    return spans.readback_mean_ms(run, "queue")
