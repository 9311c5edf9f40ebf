"""Junction and dispatch: mean ms a step that the fetch of the device's loss
counters takes under the controller lock (an `int()` every 64th step, which
waits for every step dispatched so far): the join's `pairs_dropped`, the
pattern's `pending_dropped`, the time window's two loss counters. Source: the
cells `stage_ms.drop_sync` of `statistics_report()["joins" | "patterns" |
"windows"][<query>]` (spans `siddhi.join.drop_sync`,
`siddhi.pattern.drop_sync`, `siddhi.window.drop_sync`) over those sections'
`steps`, both as deltas. A program or a deployment without such a section
leaves nothing to read."""


def _steps(entry: dict) -> float:
    steps = entry.get("steps", 0)  # per side or stream, or one count
    return float(sum(steps.values()) if isinstance(steps, dict) else steps)


def read(run: dict):
    ms = steps = 0.0
    for section in ("joins", "patterns", "windows"):
        before = run["stats0"].get(section) or {}
        for name, z in (run["stats1"].get(section) or {}).items():
            a = before.get(name) or {}
            cell_a = a.get("stage_ms", {}).get("drop_sync")
            cell_z = z.get("stage_ms", {}).get("drop_sync")
            if cell_a and cell_z:
                ms += cell_z["total_ms"] - cell_a["total_ms"]
                steps += _steps(z) - _steps(a)
    return ms / steps if steps > 0 else None
