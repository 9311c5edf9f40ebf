"""Device step: device ms a batch spent in operations traced inside
`siddhi.table` (a table's device index, since PR 44: the key's slot, the
events of a key in order, the rows written) over the programs that run it —
the join's probe programs (`jit_join_probe_left/right`, whose index lookup
books there) and the table-write programs (`jit_table_write_<table>`):
their `siddhi.table` operation time within whole executions inside the
benchmark's two markers, over those executions. Scopes are read as
stages.py reads them (the `op_name` of the trace json beside the xplane).
None off the chip, without a trace, from unscoped programs, and where no
such program ran or none names the stage."""
import bisect

import stages
import trace_reduce

PROGRAMS = ("jit_join_probe_left(", "jit_join_probe_right(",
            "jit_table_write_")
STAGE = "table"


def table_ms(path: str):
    """(ms a batch in the stage, executions) from one trace, or None."""
    json_path = stages.trace_json_beside(path)
    if json_path is None:
        return None
    profile = trace_reduce.load(path)
    scopes = stages.load_scopes(json_path)
    lo = trace_reduce.find_marker(profile, trace_reduce.MARK_OPEN)
    hi = trace_reduce.find_marker(profile, trace_reduce.MARK_CLOSE)
    executions, mine = 0, 0.0
    found = False
    for chip in trace_reduce.device_lines(profile):
        ops = sorted(chip["ops"], key=lambda e: (e[1], -e[2]))
        starts = [e[1] for e in ops]
        for name, a, z in chip["modules"]:
            if (lo is not None and a < lo) or (hi is not None and z > hi) \
                    or not name.startswith(PROGRAMS):
                continue
            executions += 1
            inside = [e for e in ops[bisect.bisect_left(starts, a):
                                     bisect.bisect_left(starts, z)]
                      if e[2] <= z]
            for op, own in stages._own_times(inside):
                stage, _ = stages.scope_of(scopes.get((name, op)))
                if stage == STAGE:
                    found = True
                    mine += own
    if not executions or not found:
        return None
    return mine / 1e6 / executions, executions


def read(run: dict):
    if (run.get("device") or {}).get("platform") != "tpu" \
            or not run.get("trace") or not run.get("reduced_trace"):
        return None
    if "table_stage" not in run:
        path = trace_reduce.newest_xplane(run["trace_dir"])
        run["table_stage"] = table_ms(path) if path else None
    found = run["table_stage"]
    return None if found is None else found[0]
