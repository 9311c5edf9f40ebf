"""What the readers of the program's stage spans share (PR 24). The program
books every span twice: into cumulative cells beside `stage_ms` (read as
deltas across the window, like `layers.stage_mean_ms`), and as a
`siddhi.*` event in the host plane of the profiler's trace, on the clock of
the device's operations. A program without them (a parent commit) has no
such cell and no such event: every function here then returns None."""

from __future__ import annotations

import numpy as np

import layers
import trace_reduce

SHORT_GAP_NS = 100_000  # run.py's: a shorter gap lies between two operations
FEEDER = "siddhi.feeder."  # fill, h2d, lock_wait, dispatch: one thread's states


def stage_cpu_mean_ms(run: dict, stage: str):
    """layers.stage_mean_ms for the thread's CPU time of a stage (`cpu_ms`):
    wall minus this is time the thread did not run."""
    ms = units = 0.0
    for a, z in zip(layers.pipelines(run["stats0"], run),
                    layers.pipelines(run["stats1"], run)):
        a = a.get("stage_ms", {}).get(stage)
        z = z.get("stage_ms", {}).get(stage)
        if a and z and "cpu_ms" in a and "cpu_ms" in z:
            ms += z["cpu_ms"] - a["cpu_ms"]
            units += z["batches"] - a["batches"]
    return ms / units if units > 0 else None


def readback_mean_ms(run: dict, stage: str):
    """Mean wall per batch of one read-back stage over the window, from
    `statistics_report()["readback"]["stage_ms"]`."""
    a = (run["stats0"].get("readback") or {}).get("stage_ms", {}).get(stage)
    z = (run["stats1"].get("readback") or {}).get("stage_ms", {}).get(stage)
    if not (a and z) or z["batches"] <= a["batches"]:
        return None
    return (z["total_ms"] - a["total_ms"]) / (z["batches"] - a["batches"])


def host_spans(run: dict):
    """name -> sorted, merged (start, end) of the traced slice's `siddhi.*`
    host events, on the clock of `reduced_trace["gaps"]` (monotonic). None
    without a trace; read once a run."""
    if "host_spans" in run:
        return run["host_spans"]
    reduced = run.get("reduced_trace")
    path = trace_reduce.newest_xplane(run["trace_dir"]) \
        if run.get("trace") and reduced else None
    spans = None
    if path is not None:
        # as the gaps: on the trace's own clock where no offset is known
        offset = reduced.get("clock_offset_ns") or 0
        found: dict = {}
        for plane in trace_reduce.load(path).planes:
            if plane.name.startswith(trace_reduce.DEVICE_PLANE):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("siddhi."):
                        found.setdefault(ev.name, []).append(
                            (ev.start_ns - offset,
                             ev.start_ns + ev.duration_ns - offset))
        spans = {name: trace_reduce.union(v) for name, v in found.items()}
    run["host_spans"] = spans
    return spans


def idle_share_pct(run: dict, state: str):
    """Of the slice's device idle time in gaps of at least SHORT_GAP_NS, the
    share during which the feeder was in `state`."""
    spans = host_spans(run)
    if not spans or not any(name.startswith(FEEDER) for name in spans):
        return None
    gaps = [(a, z) for a, z in run["reduced_trace"].get("gaps", [])
            if z - a >= SHORT_GAP_NS]
    idle = sum(z - a for a, z in gaps)
    if not idle:
        return None
    mine = np.asarray(spans.get(FEEDER + state, []), np.float64).reshape(-1, 2)
    covered = sum(float(np.clip(np.minimum(mine[:, 1], z)
                                - np.maximum(mine[:, 0], a), 0, None).sum())
                  for a, z in gaps)
    return 100.0 * covered / idle
