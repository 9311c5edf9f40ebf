"""From a jax.profiler trace (`*.xplane.pb`) to the numbers the benchmark
reports: device busy seconds (the union of the intervals in which an
operation ran on the device), per-operation and per-program sums, and the
idle gaps — all clipped to the window between the benchmark's own two
markers, which also tie the trace's clock to `time.monotonic_ns`.

What a TPU trace looks like (looked at by hand, PR 22; `--describe` prints
the same for any file): one plane per chip named `/device:TPU:<n>`, whose
line `XLA Ops` holds one event per executed HLO operation and whose line
`XLA Modules` holds one event per executed program (`jit_<name>(<id>)`);
host threads are lines of the plane `/host:CPU`, where the markers land. On
the CPU backend there is no device plane: the operations are host events
that carry an `hlo_module` stat, which only the rehearsal (`host_ops`) reads
in their place; a measured run whose trace has no device plane is refused.

    python benchmarks/trace_reduce.py --describe <file.xplane.pb>
"""

from __future__ import annotations

import glob
import os
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_OPEN = "bench_trace_open"
MARK_CLOSE = "bench_trace_close"


def newest_xplane(trace_dir: str):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _stats(event) -> dict:
    return dict(event.stats)


def find_marker(profile, name: str):
    """Start (ns, trace clock) of the first host event called `name`."""
    best = None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name and (best is None or ev.start_ns < best):
                    best = ev.start_ns
    return best


def device_lines(profile, host_ops: bool = False) -> list:
    """Per chip: {"ops": [(name, start, end)], "modules": [...]}. A trace
    with no device plane has no chip in it; only where `host_ops` is asked
    for (the CPU backend's rehearsal) is one pseudo-chip made of the host
    events that carry an `hlo_module` stat."""
    chips = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        chip = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key:
                chip[key] = [(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events]
        if chip["ops"]:
            chips.append(chip)
    if chips or not host_ops:
        return chips
    ops = []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0 and not ev.name.startswith("end:"):
                    module = _stats(ev).get("hlo_module")
                    if module:
                        ops.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
    return [{"ops": ops, "modules": []}] if ops else []


def union(intervals: list) -> list:
    """Sorted, merged (start, end) intervals."""
    out: list = []
    for a, z in sorted(intervals):
        if out and a <= out[-1][1]:
            if z > out[-1][1]:
                out[-1] = (out[-1][0], z)
        else:
            out.append((a, z))
    return out


def clip(events: list, lo: float, hi: float) -> list:
    return [(n, max(a, lo), min(z, hi)) for n, a, z in events
            if z > lo and a < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of merged `busy` intervals inside [lo, hi]."""
    out = []
    at = lo
    for a, z in busy:
        if a > at:
            out.append((at, a))
        at = max(at, z)
    if hi > at:
        out.append((at, hi))
    return out


def short_name(name: str, limit: int = 96) -> str:
    """An `XLA Ops` event is named by its whole HLO text; its head says
    which op it is and what shape it makes."""
    return name if len(name) <= limit else name[:limit - 3] + "..."


def sums(events: list) -> dict:
    """name -> [seconds, count]."""
    out: dict = {}
    for n, a, z in events:
        cell = out.setdefault(short_name(n), [0.0, 0])
        cell[0] += (z - a) / 1e9
        cell[1] += 1
    return out


def reduce_profile(profile, mono_open_ns=None, host_ops=False) -> dict:
    """The reduction. The window is what lies between the two markers; a
    trace without them (a recorded fixture) is reduced over the span of its
    device events. `clock_offset_ns` is trace clock minus monotonic clock at
    the opening marker, so a gap can be laid beside the producers' and the
    callback's logs."""
    chips = device_lines(profile, host_ops)
    lo, hi = find_marker(profile, MARK_OPEN), find_marker(profile, MARK_CLOSE)
    if lo is None or hi is None:
        every = [t for c in chips for _, a, z in c["ops"] for t in (a, z)]
        if not every:
            return {"chips": 0, "window_s": 0.0, "busy_s": 0.0}
        lo, hi = min(every), max(every)
    busy_s = []
    ops_all: list = []
    modules_all: list = []
    idle: list = []
    for chip in chips:
        ops = clip(chip["ops"], lo, hi)
        merged = union([(a, z) for _, a, z in ops])
        busy_s.append(sum(z - a for a, z in merged) / 1e9)
        ops_all += ops
        # whole executions only: a program cut by the window's edge would
        # count as one execution of part of its time
        modules_all += [(m, a, z) for m, a, z in chip["modules"]
                        if a >= lo and z <= hi]
        idle += gaps(merged, lo, hi)
    n = max(len(chips), 1)
    offset = None if mono_open_ns is None else lo - mono_open_ns
    return {
        "chips": len(chips),
        "window_s": (hi - lo) / 1e9,
        # averaged over the chips used
        "busy_s": sum(busy_s) / n,
        "op_seconds": {k: [v[0] / n, v[1]] for k, v in sums(ops_all).items()},
        "module_seconds": {k: [v[0] / n, v[1]]
                           for k, v in sums(modules_all).items()},
        "op_total_s": sum((z - a) for _, a, z in ops_all) / 1e9 / n,
        # idle gaps, longest first, as (start, end) on the monotonic clock
        # where the offset is known, else on the trace's
        "gaps": sorted(((a - (offset or 0), z - (offset or 0))
                        for a, z in idle), key=lambda g: g[0] - g[1]),
        "clock_offset_ns": offset,
    }


def reduce_file(path: str, mono_open_ns=None, host_ops=False) -> dict:
    return reduce_profile(load(path), mono_open_ns, host_ops)


def describe(path: str, out=sys.stdout) -> None:
    """Planes, lines, event counts and the first events of each line."""
    for plane in load(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}", file=out)
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}", file=out)
            for ev in events[:5]:
                print(f"    {ev.name[:90]!r} start={ev.start_ns:.0f} "
                      f"dur={ev.duration_ns:.0f} "
                      f"stats={ {k: str(v)[:40] for k, v in list(_stats(ev).items())[:6]} }",
                      file=out)


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--describe":
        describe(sys.argv[2])
    else:
        sys.exit(__doc__)
