"""What the per-layer readers share: deltas of the program's cumulative
counters across the measured window. A reader gets the whole `run` (see
run.py `run_cell`): the statistics at the window's two ends (`stats0`,
`stats1`), the producers' frame log, the callback's log, the reduced device
trace and the traced slice's own statistics, the memory peak and the compile
log."""

from __future__ import annotations


def pipelines(stats: dict, run: dict) -> list:
    """The ingress pipeline's counters of every input stream."""
    pipes = stats.get("ingress_pipeline") or {}
    return [pipes.get(plan["stream"]) or {} for plan in run["events"].plans]


def stage_mean_ms(run: dict, stage: str):
    """Mean wall per unit of one ingress stage over the window, from the
    cumulative `stage_ms.<stage>` cells (total_ms, batches), over all input
    streams."""
    ms = units = 0.0
    for a, z in zip(pipelines(run["stats0"], run),
                    pipelines(run["stats1"], run)):
        a = a.get("stage_ms", {}).get(stage)
        z = z.get("stage_ms", {}).get(stage)
        if a and z:
            ms += z["total_ms"] - a["total_ms"]
            units += z["batches"] - a["batches"]
    return ms / units if units > 0 else None


def slice_batches(run: dict):
    """Batches the feeders delivered inside the traced slice."""
    trace = run.get("trace")
    if not trace:
        return None
    n = 0
    for a, z in zip(pipelines(trace["stats_open"], run),
                    pipelines(trace["stats_close"], run)):
        n += z.get("batches_delivered", 0) - a.get("batches_delivered", 0)
    return n if n > 0 else None
