"""Device time of the step programs by stage (PR 34). The program cuts every
step program's body into named stages with `jax.named_scope("siddhi.<stage>")`
(`siddhi_tpu/telemetry/tracing.py` `STEP_STAGES`); the scope goes into the
`op_name` of every HLO operation traced inside it, and this file books each
device operation's time to the stage its `op_name` names.

    python benchmarks/stages.py <file.xplane.pb>

prints the two-level tree with counts (next to `trace_reduce.py --describe`).

**Where the scope is read from** (settled on the chip, PR 34): a TPU plane's
`XLA Ops` events carry three stats through `jax.profiler.ProfileData`
(`device_offset_ps`, `device_duration_ps`, `Time Scale Multiplier`) and no
`op_name`; the `*.trace.json.gz` the profiler writes beside the xplane has it
as `args.tf_op` (`"jit(step)/siddhi.selector/siddhi.selector/sort/sort:"`)
next to `args.long_name`, which is the xplane event's own name. So times come
from the xplane, as everywhere in this directory, and the json gives one map
per run: (program, operation) -> `op_name`.

**What is counted.** The `XLA Ops` events that lie inside whole executions,
within the benchmark's two markers, of the cell's step programs: the family
(`jit_pattern_step_*`, `jit_join_probe_left/right`, `jit_step`) with the most
device time in the slice; of several `jit_step` programs the costliest, as
the rooflines take it. An operation's time is its own (an event nested in it
is taken out) and goes to the first `siddhi.<stage>` component of its
`op_name`: a shared kernel's sub-scope (`siddhi.selector/sort`) never takes an
operation away from the stage of the step that called it. An operation with
no such component is `unattributed`. XLA gives a fusion the metadata of its
root, so work fused across a stage's edge is booked to the root's stage;
`unattributed_pct` and the sum (stages + unattributed = the programs'
operation time) bound what that can hide.

**Unscoped programs.** jax strips debug info from the compile cache's key, so
an executable loaded from a cache that an unscoped build wrote carries no
scope. A run none of whose counted operations names a `siddhi.` stage reports
`None` for every stage (never 0) and says so: `scoped: false` here, and
`step_stages` under `cache` on the run's `detail` line.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import sys
import time

import trace_reduce

PREFIX = "siddhi."
# family -> (program name prefixes, its stages in program order)
FAMILIES = {
    "pattern": (("jit_pattern_step_",),
                ("filter", "append", "match", "frames", "selector", "emit")),
    "join": (("jit_join_probe_left(", "jit_join_probe_right("),
             ("filter", "window", "probe", "compact", "frames", "selector",
              "emit")),
    "query": (("jit_step(",),
              ("filter", "window", "selector", "emit")),
}
SUBSTAGES = {"window": ("append", "expire", "fetch"),
             "selector": ("sort", "gather", "scan", "scatter"),
             "match": ("expire",)}
UNATTRIBUTED = "unattributed"
WIDEST = 4  # operations named per stage or part by `describe`


def scope_of(op_name):
    """(stage, `stage/sub` or None) an `op_name` names; (None, None) where
    it names no stage."""
    if not op_name:
        return None, None
    parts = op_name.rstrip(":").split("/")
    stage = next((p[len(PREFIX):] for p in parts if p.startswith(PREFIX)),
                 None)
    if stage is None:
        return None, None
    own = PREFIX + stage
    for i, part in enumerate(parts[:-1]):
        if part == own and parts[i + 1] in SUBSTAGES.get(stage, ()):
            return stage, f"{stage}/{parts[i + 1]}"
    return stage, None


def trace_json_beside(xplane_path: str):
    found = glob.glob(os.path.join(os.path.dirname(xplane_path),
                                   "*.trace.json.gz"))
    return max(found, key=os.path.getmtime) if found else None


def load_scopes(json_path: str) -> dict:
    """(program, operation) -> `op_name`, from the trace viewer's json: its
    device process's `XLA Ops` events carry `long_name` (the xplane event's
    name) and, where the HLO has one, `tf_op`; the program is the `XLA
    Modules` event an operation lies in."""
    with gzip.open(json_path) as fh:
        events = json.load(fh).get("traceEvents", [])
    devices, lines = set(), {}
    for e in events:
        if e.get("ph") != "M":
            continue
        name = (e.get("args") or {}).get("name", "")
        if e.get("name") == "process_name" \
                and name.startswith(trace_reduce.DEVICE_PLANE):
            devices.add(e["pid"])
        elif e.get("name") == "thread_name":
            lines[(e["pid"], e.get("tid"))] = name
    modules: dict = {}  # pid -> sorted [(start, end, name)]
    ops = []
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in devices:
            continue
        line = lines.get((e["pid"], e.get("tid")))
        if line == trace_reduce.MODULES_LINE:
            modules.setdefault(e["pid"], []).append(
                (e["ts"], e["ts"] + e.get("dur", 0.0), e["name"]))
        elif line == trace_reduce.OPS_LINE:
            ops.append(e)
    for spans in modules.values():
        spans.sort()
    starts = {pid: [s[0] for s in spans] for pid, spans in modules.items()}
    scopes: dict = {}
    for e in ops:
        args = e.get("args") or {}
        spans = modules.get(e["pid"])
        if not spans or "long_name" not in args:
            continue
        i = bisect.bisect_right(starts[e["pid"]], e["ts"]) - 1
        if i >= 0 and e["ts"] <= spans[i][1]:
            scopes.setdefault((spans[i][2], args["long_name"]),
                              args.get("tf_op"))
    return scopes


def _own_times(ops: list) -> list:
    """[(name, own ns)] of events sorted by start: a nested event's time is
    taken out of the event it lies in."""
    out, stack = [], []  # stack of [end, index into out]
    for name, a, z in ops:
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(z, stack[-1][0]) - a
        out.append([name, z - a])
        stack.append([z, len(out) - 1])
    return out


def reduce_profile(profile, scopes: dict):
    """The reduction, or None where the slice ran no step program on a
    device. `scopes` is `load_scopes`' map."""
    chips = trace_reduce.device_lines(profile)
    lo = trace_reduce.find_marker(profile, trace_reduce.MARK_OPEN)
    hi = trace_reduce.find_marker(profile, trace_reduce.MARK_CLOSE)
    if lo is None or hi is None:  # a recorded fixture: its events' span
        every = [t for c in chips for _, a, z in c["ops"] for t in (a, z)]
        if not every:
            return None
        lo, hi = min(every), max(every)
    # whole executions only, per family and program
    found: dict = {}
    for c, chip in enumerate(chips):
        for name, a, z in chip["modules"]:
            if a < lo or z > hi:
                continue
            family = next((f for f, (prefixes, _) in FAMILIES.items()
                           if name.startswith(prefixes)), None)
            if family:
                found.setdefault(family, {}).setdefault(name, []).append(
                    (c, a, z))
    if not found:
        return None
    if "query" in found:  # the costliest `jit_step` alone
        name = max(found["query"], key=lambda n: sum(
            z - a for _, a, z in found["query"][n]))
        found["query"] = {name: found["query"][name]}
    family = max(found, key=lambda f: sum(
        z - a for runs in found[f].values() for _, a, z in runs))
    sorted_ops = []
    for chip in chips:
        ops = sorted(chip["ops"], key=lambda e: (e[1], -e[2]))
        sorted_ops.append((ops, [e[1] for e in ops]))
    by_stage: dict = {}
    by_sub: dict = {}
    by_op: dict = {}  # (stage or part, operation) -> [ns, count]
    executions = 0
    module_ns = op_ns = 0.0
    scoped = False
    for program, runs in found[family].items():
        for c, a, z in runs:
            executions += 1
            module_ns += z - a
            ops, starts = sorted_ops[c]
            inside = [e for e in ops[bisect.bisect_left(starts, a):
                                     bisect.bisect_left(starts, z)]
                      if e[2] <= z]
            for name, own in _own_times(inside):
                stage, sub = scope_of(scopes.get((program, name)))
                op_ns += own
                scoped = scoped or stage is not None
                stage = stage or UNATTRIBUTED
                op = (sub or stage, trace_reduce.short_name(name))
                for key, table in ((stage, by_stage), (sub, by_sub),
                                   (op, by_op)):
                    if key is not None:
                        cell = table.setdefault(key, [0.0, 0])
                        cell[0] += own
                        cell[1] += 1
    per = 1e6 * executions  # ns a slice -> ms an execution

    def ms(table: dict) -> dict:
        return {k: [v[0] / per, v[1]] for k, v in table.items()}

    stages = ms(by_stage)
    widest: dict = {}
    for (where, name), (ns, count) in sorted(by_op.items(),
                                             key=lambda kv: -kv[1][0]):
        if len(widest.setdefault(where, [])) < WIDEST:
            widest[where].append([name, ns / per, count])
    return {
        "family": family,
        "programs": {n: [sum(z - a for _, a, z in runs) / 1e9, len(runs)]
                     for n, runs in found[family].items()},
        "executions": executions,
        "scoped": scoped,
        "module_ms": module_ns / per,
        "op_ms": op_ns / per,
        "unattributed_pct": 100.0 * by_stage.get(
            UNATTRIBUTED, [0.0])[0] / op_ns if op_ns else None,
        # per execution: stage -> [ms, operations in the slice]
        "stage_ms": {s: stages.get(s, [0.0, 0])
                     for s in FAMILIES[family][1] + (UNATTRIBUTED,)},
        "sub_ms": ms(by_sub),
        # for people: stage or part -> its widest operations,
        # [name, ms per execution, count in the slice]
        "widest_ops": widest,
    }


def reduce_file(xplane_path: str, json_path=None):
    json_path = json_path or trace_json_beside(xplane_path)
    if json_path is None:
        return None
    return reduce_profile(trace_reduce.load(xplane_path),
                          load_scopes(json_path))


def of(run: dict):
    """The traced slice's reduction, once a run (kept in `run`), or None:
    off the chip, without a trace, where the profiler left no json beside
    the xplane, or where no step program ran. What it found goes on the
    `detail` line too, as `step_stages` under `cache`: the programs, whether
    they carry scopes, and the seconds the reduction took."""
    if "stages" in run:
        return run["stages"]
    out = None
    if run["device"]["platform"] == "tpu" and run.get("trace") \
            and run.get("reduced_trace"):
        path = trace_reduce.newest_xplane(run["trace_dir"])
        t0 = time.monotonic()
        out = reduce_file(path) if path else None
        note = {"reduce_s": time.monotonic() - t0}
        if out is None:
            note["read"] = "no step program or no trace.json.gz in the slice"
        else:
            note.update(programs=out["programs"], scoped=out["scoped"],
                        op_ms=out["op_ms"], module_ms=out["module_ms"])
            if not out["scoped"]:
                note["read"] = ("the step programs carry no siddhi.* scope "
                                "(built before PR 34, or loaded from a "
                                "compile cache such a build wrote): every "
                                "stage metric is None, not 0")
        if isinstance(run.get("cache"), dict):
            run["cache"]["step_stages"] = note
    run["stages"] = out
    return out


def stage_ms(run: dict, stage: str):
    """Device ms per execution of the cell's step programs inside `stage`;
    None from unscoped programs and for a stage the family does not have."""
    found = of(run)
    if not found or not found["scoped"] or stage not in found["stage_ms"]:
        return None
    return found["stage_ms"][stage][0]


def unattributed_pct(run: dict):
    found = of(run)
    return found["unattributed_pct"] if found and found["scoped"] else None


def describe(found, out=sys.stdout) -> None:
    if found is None:
        print("no step program in the trace, or no trace.json.gz beside it",
              file=out)
        return
    print(f"{found['family']} step: {found['executions']} whole executions; "
          f"per execution {found['module_ms']:.3f} ms on `XLA Modules`, "
          f"{found['op_ms']:.3f} ms of operations", file=out)
    for name, (seconds, count) in found["programs"].items():
        print(f"  {name}: {count} x {1e3 * seconds / count:.3f} ms", file=out)
    if not found["scoped"]:
        print("  the programs carry no siddhi.* scope: built before PR 34 or "
              "loaded from a compile cache such a build wrote", file=out)
        return
    def ops_of(where: str, indent: str) -> None:
        for name, ms, count in found["widest_ops"].get(where, []):
            print(f"{indent}{ms:9.3f} ms {count:6d} x {name}", file=out)

    parts = sorted(found["sub_ms"].items())
    for stage, (ms, count) in found["stage_ms"].items():
        share = 100.0 * ms / found["op_ms"] if found["op_ms"] else 0.0
        print(f"  {PREFIX + stage if stage != UNATTRIBUTED else stage:24s}"
              f"{ms:10.3f} ms {share:6.2f} %  {count:7d} ops", file=out)
        ops_of(stage, " " * 8)
        for part, (part_ms, part_count) in parts:
            if part.split("/")[0] == stage:
                print(f"    {PREFIX + part:22s}{part_ms:10.3f} ms"
                      f"          {part_count:7d} ops", file=out)
                ops_of(part, " " * 10)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    describe(reduce_file(sys.argv[1]))
