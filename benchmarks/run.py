#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

Builds the cell's deployment (the served path: SXF1 frames over a real
socket -> @Async ingress -> jitted steps on the device -> async read-back ->
columnar callback), warms the cell's own shapes, starts the producer
processes, measures for `--seconds`, drains, checks every delivered row's
conservation and a seeded sample against the plain reference, and prints as
its LAST stdout line one JSON object: `correct`, `attempted`, `failed`,
`metrics`, `device`, and with `--trace 1` `breakdown`. Everything else it
has to say goes on earlier lines (one JSON `detail` line) and to stderr.

It refuses any platform but `tpu` (exit 2, no result line). `--rehearse`
runs the same body at toy sizes on the CPU backend and says so in
`device.platform`; such a line is never recorded and never quoted.

Everything that belongs to one cell is found by name (registry.py); this
file knows no configuration, traffic mix or metric by name.
"""

from __future__ import annotations

import time

T_PROCESS_START_NS = time.monotonic_ns()  # set-up counts from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))  # the checkout: the program

import registry  # noqa: E402
from registry import BenchmarkError  # noqa: E402

OUT_DIR = os.path.join(BENCH_DIR, "out")
TRACE_START_SHARE = 0.4  # of the window, where the traced slice opens
TRACE_SECONDS = 4.0      # at most; and at most 0.3 of the window
POST_TIMEOUT_S = 120.0
DRAIN_TIMEOUT_S = 180.0
SHORT_GAP_NS = 100_000  # idle gaps under 0.1 ms are not laid to the host
SETTLE_TIMEOUT_S = 20.0  # after drain(), for the last callbacks to return


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the producers


class Producers:
    """The load generator's processes. They never touch the chip: their
    environment pins jax (which they do not import) to the CPU."""

    def __init__(self, specs: list) -> None:
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": BENCH_DIR}
        self.procs = [subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "loadgen.py"),
             json.dumps(spec)], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, env=env, cwd=BENCH_DIR)
            for spec in specs]

    def wait_ready(self) -> None:
        for p in self.procs:
            line = p.stdout.readline().strip()
            if line != "ready":
                raise BenchmarkError(f"a producer said {line!r}, not ready")

    def go(self, port: int, t0_ns: int, t_end_ns: int) -> None:
        for p in self.procs:
            p.stdin.write(f"go {port} {t0_ns} {t_end_ns}\n")
            p.stdin.flush()

    def logs(self, timeout: float) -> list:
        out = []
        for p in self.procs:
            stdout, _ = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise BenchmarkError(
                    f"a producer exited with {p.returncode}")
            out.append(json.loads(stdout.strip().splitlines()[-1]))
        return out

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for p in self.procs:
            p.wait()
            for pipe in (p.stdin, p.stdout):
                if pipe and not pipe.closed:
                    pipe.close()


# ------------------------------------------------------------------ the run


def device_gate(chips: int, rehearse: bool) -> dict:
    """The device as jax reports it; exits the run where it is not what the
    cell needs."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:  # jax found no backend it may use
        raise BenchmarkError(f"jax found no usable device: {e!r}") from None
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        raise BenchmarkError(
            f"requires a TPU, but jax.devices()[0] is {dev.platform!r} "
            f"({dev.device_kind}); refusing to measure there")
    if rehearse and dev.platform != "cpu":
        raise BenchmarkError("--rehearse is for the CPU backend")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell needs {chips} chips, jax sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def traced_slice(dep, seconds: float, t0_ns: int, trace_dir: str) -> dict:
    """Open the benchmark's own bounded trace inside the window, mark both
    ends on the monotonic clock, and note the pipeline's counters at both
    marks (so device time can be put over the batches of the slice)."""
    import jax

    import trace_reduce
    length = min(TRACE_SECONDS, 0.3 * seconds)
    wait = t0_ns / 1e9 + TRACE_START_SHARE * seconds - time.monotonic()
    time.sleep(max(0.0, wait))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host Python frames: large, unread
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reduce.MARK_OPEN):
        open_ns = time.monotonic_ns()
    stats_open = dep.statistics()
    time.sleep(length)
    stats_close = dep.statistics()
    with jax.profiler.TraceAnnotation(trace_reduce.MARK_CLOSE):
        pass
    jax.profiler.stop_trace()
    return {"open_ns": open_ns, "stats_open": stats_open,
            "stats_close": stats_close}


def run_cell(cell: dict, args, device: dict) -> tuple:
    """Returns (result line, detail)."""
    import numpy as np

    import deployment
    import metrics
    import record

    rehearse = args.rehearse
    config, traffic, own = cell["config"], cell["traffic"], cell["own"]
    sizes = config["rehearse_sizes"] if rehearse else config["sizes"]
    config = {**config, "sizes": sizes}
    rate = args.rate or own.get(
        "rehearse_rate_events_per_s" if rehearse else "rate_events_per_s")
    mode = traffic["mode"]
    if mode == "paced" and not rate:
        raise BenchmarkError(f"cell {cell['name']} is paced and names no "
                             "rate_events_per_s")
    warm_frames = config["warm_frames"]
    reference = registry.load_module("references", config["reference"])
    events = record.Events(registry.stream_plans(config, traffic, rehearse),
                           args.seed, warm_frames)
    streams = [plan["stream"] for plan in events.plans]

    producers = None
    dep = None
    trap = deployment.EngineLogTrap()
    engine_log = logging.getLogger("siddhi_tpu")
    engine_log.addHandler(trap)
    fails: list = []
    try:
        import siddhi_tpu.native
        from siddhi_tpu.util.platform import configure_compile_cache
        cache_dir = configure_compile_cache()
        clog = deployment.CompileLog()
        delivered_log = record.Delivered()
        dep = deployment.Deployment(config, sizes, delivered_log.on_block)
        from siddhi_tpu.io import wire
        typecodes = {s: [code for _, _, code in wire.schema_plan(
            dep.rt.junctions[s].definition)] for s in streams}
        # the producers build their pools while this process warms up
        producers = Producers([{
            **spec, "host": "127.0.0.1", "mode": mode, "rate": rate,
            "post_timeout_s": POST_TIMEOUT_S,
            "typecodes": typecodes[spec["stream"]],
            "path": dep.stream_path(spec["stream"]),
        } for spec in events.producer_specs()])
        if not siddhi_tpu.native.available():
            fails.append("the native module did not load")
        warmed = dep.warm(config.get("warm_buckets", []))

        sent_extra: dict = {}

        def own_frame(f: int) -> None:
            """Post a frame of the parent's own (warm-up, closing)."""
            stream = events.plan_of(f)["stream"]
            sent_extra[f] = dep.post(
                stream, events.wire_frame(f, typecodes[stream]),
                POST_TIMEOUT_S)

        # per stream: the first frame alone, then the rest
        for f in range(events.warm_total):
            own_frame(f)
            if f % warm_frames in (0, warm_frames - 1):
                dep.rt.drain(timeout=DRAIN_TIMEOUT_S)
        for stream in streams:
            if stream not in dep.statistics()["ingress_pipeline"]:
                fails.append("the ingress pipeline did not engage for "
                             + stream)
        producers.wait_ready()

        # ---- the measured window
        setup_mark = clog.mark()
        retraces0 = dep.engine_compiles()
        t0_ns = time.monotonic_ns() + 200_000_000
        t_end_ns = t0_ns + int(args.seconds * 1e9)
        producers.go(dep.port, t0_ns, t_end_ns)
        time.sleep(max(0.0, (t0_ns - time.monotonic_ns()) / 1e9))
        stats0 = dep.statistics()
        setup_s = (t0_ns - T_PROCESS_START_NS) / 1e9
        trace = None
        trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace = traced_slice(dep, args.seconds, t0_ns, trace_dir)
        time.sleep(max(0.0, (t_end_ns - time.monotonic_ns()) / 1e9))
        stats1 = dep.statistics()
        window_mark = clog.mark()
        retraces1 = dep.engine_compiles()
        logs = producers.logs(timeout=POST_TIMEOUT_S + 30)

        # ---- after the window: push the last window out, drain, count
        frames = record.merge_frame_logs(logs, events)
        next_frame = int(frames["frame"].max()) + 1 if frames["frame"].size \
            else events.warm_total
        closing = config.get("closing_frames", 0)
        for i in range(closing * len(streams)):
            s, slot = divmod(i, closing)
            events.overrides[next_frame + i] = (
                s, events.plans[s]["producers"], warm_frames + slot)
            own_frame(next_frame + i)
        dep.rt.drain(timeout=DRAIN_TIMEOUT_S)
        run = {
            "cell": cell, "config": config, "reference": reference,
            "events": events, "frames": frames, "sent_extra": sent_extra,
            "mode": mode, "rate": rate, "seconds": args.seconds,
            "t0_ns": t0_ns, "t_end_ns": t_end_ns, "device": device,
        }
        # drain() comes back while the last block's callback is still
        # running (PERF.md open questions); a client waits for its results
        sent = list(sent_extra) + frames["frame"][
            frames["status"] == 200].tolist()
        delivered_log.wait_rows(reference.expected_output_rows(run, sent),
                                SETTLE_TIMEOUT_S)
        t_drained_ns = time.monotonic_ns()
        run["stats_end"] = stats_end = dep.statistics()
        run["memory_peak_bytes"] = peak = memory_peak_bytes(cell["chips"])
        run["delivered"] = delivered = delivered_log.arrays()

        t_check = time.monotonic()
        account = reference.account(run)
        sample = reference.verify_sample(run,
                                         np.random.default_rng(args.seed))
        fails += account["failures"] + sample["failures"]
        check_s = time.monotonic() - t_check
    finally:
        if producers is not None:
            producers.stop()
        if dep is not None:
            try:
                dep.close()
            except Exception as e:  # noqa: BLE001 — reported, run goes on
                fails.append(f"teardown: {e!r}")
        engine_log.removeHandler(trap)
    fails += [f"engine log: {m}" for m in trap.tripped]

    in_window = clog.between(setup_mark, window_mark)
    run.update({
        # one sample per block delivered in the window, from the due time
        "latency_ms": metrics.block_latency_ms(frames, delivered,
                                               events.stride, t0_ns,
                                               t_end_ns),
        "stats0": stats0, "stats1": stats1,
        "trace": trace, "trace_dir": trace_dir, "rehearse": rehearse,
        "setup_s": setup_s,
        "setup_programs": clog.between(0, setup_mark),
        "window_programs": in_window,
        "window_retraces": retraces1 - retraces0,
        "cache": {"dir": cache_dir, "hits": clog.cache_hits,
                  "misses": clog.cache_misses,
                  "requests": clog.cache_requests},
    })
    every = cell["manifest_metrics"]
    e2e = read_metrics("end_to_end", every["end_to_end"], run)
    if args.trace:
        reduce_trace(run, fails)
    # untraced too, for the detail line: the readers of the device trace
    # find nothing there and return None
    layer_values = read_metrics("layer_metrics", every["per_layer"], run)
    values = layer_values if args.trace else e2e
    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    result = {
        "correct": not fails,
        "attempted": account["attempted"],
        "failed": account["failed"],
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in wanted if values.get(m["name"]) is not None},
        "device": {**device, "memory_peak_bytes": peak},
    }
    if args.trace:
        reduced = run["reduced_trace"]
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = breakdown_of(run, reduced)
    detail = {
        "detail": cell["name"], "seed": args.seed, "trace": args.trace,
        "failures": fails, "checks": account["checks"],
        "account": account["detail"],
        "sample": {k: v for k, v in sample.items() if k != "failures"},
        "frames_logged": int(frames["frame"].size),
        "reconnects": int(frames["reconnects"].sum()),
        "post_ms_max": float(metrics.post_ms(frames).max(initial=0.0)),
        "blocks_delivered": len(delivered["blocks"]),
        "latency_samples": int(run["latency_ms"].size),
        # every metric the manifest names, whether or not this cell
        # reports it on its result line
        "end_to_end": e2e, "per_layer": layer_values,
        "events_per_s_by_quarter": metrics.rate_by_part(run),
        "warmed": warmed, "cache": run["cache"],
        "setup_program_s": sum(s for _, s in run["setup_programs"]),
        "window_programs": [n for n, _ in in_window],
        "window_retraces": run["window_retraces"],
        "compile_widths": stats_end.get("compile_widths"),
        "drain_s": (t_drained_ns - t_end_ns) / 1e9, "check_s": check_s,
        "backlog": metrics.backlog(run),
    }
    return result, detail


def read_metrics(kind: str, entries: list, run: dict) -> dict:
    """Each metric's own reader (`<kind>/<name>.py`, `read(run)`) takes its
    number from the run. A reader that finds nothing to read returns None
    and the metric is left out."""
    return {m["name"]: registry.load_module(kind, m["name"]).read(run)
            for m in entries}


def reduce_trace(run: dict, fails: list) -> None:
    """The traced run: reduce the profiler's trace once, for the readers
    and the breakdown. Only a rehearsal may read host events in place of a
    device plane."""
    import trace_reduce
    path = trace_reduce.newest_xplane(run["trace_dir"])
    if path is None:
        raise BenchmarkError(f"the profiler left no trace in "
                             f"{run['trace_dir']}")
    reduced = trace_reduce.reduce_file(path, run["trace"]["open_ns"],
                                       host_ops=run["rehearse"])
    if not reduced["chips"]:
        raise BenchmarkError("the trace holds no device plane "
                             f"({trace_reduce.DEVICE_PLANE}<n>)")
    if not reduced.get("busy_s"):
        fails.append("no operation ran on the device inside the traced "
                     "slice")
    run["reduced_trace"] = reduced


def breakdown_of(run: dict, reduced: dict) -> dict:
    """The device operations that took most time, under the names the trace
    prints, and the idle time by what the host was doing meanwhile. With no
    host spans in the program, a gap can only be laid beside the benchmark's
    own logs: a POST in flight, a callback running, both, or neither."""
    import numpy as np
    ops = sorted(reduced.get("op_seconds", {}).items(),
                 key=lambda kv: -kv[1][0])[:10]
    frames, dl = run["frames"], run["delivered"]
    sent = frames["status"] == 200
    posts = np.stack([frames["send_ns"][sent], frames["done_ns"][sent]], 1)
    calls = np.stack([dl["enter_ns"], dl["exit_ns"]], 1)

    def covered(spans, a: float, z: float) -> float:
        if not len(spans):
            return 0.0
        lo = np.maximum(spans[:, 0], a)
        hi = np.minimum(spans[:, 1], z)
        # spans of one kind may overlap (4 producers): merge what remains
        keep = hi > lo
        total, at = 0.0, a
        for s, e in sorted(zip(lo[keep].tolist(), hi[keep].tolist())):
            s = max(s, at)
            if e > s:
                total += e - s
                at = e
        return total

    by_state: dict = {}
    longest = []
    for a, z in reduced.get("gaps", []):  # longest first
        length = z - a
        if length < SHORT_GAP_NS:
            # between two operations of one program: no host in the way
            by_state["between_ops"] = by_state.get("between_ops", 0.0) \
                + length / 1e9
            continue
        post = covered(posts, a, z) / length >= 0.5
        call = covered(calls, a, z) / length >= 0.5
        state = {(True, True): "post_in_flight+callback_running",
                 (True, False): "post_in_flight",
                 (False, True): "callback_running",
                 (False, False): "neither"}[(post, call)]
        by_state[state] = by_state.get(state, 0.0) + length / 1e9
        if len(longest) < 5:
            longest.append([f"gap{len(longest) + 1}:{state}", length / 1e9])
    idle = sorted(([f"all:{k}", v] for k, v in by_state.items()),
                  key=lambda kv: -kv[1])
    return {"device_ops": [[name, v[0]] for name, v in ops],
            "idle_gaps": (idle + longest)[:10]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU backend; never recorded")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="find_knee.py only: offer this many events/s "
                         "instead of the cell's own rate")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(levelname)s %(name)s: %(message)s")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        cell = registry.cell(args.workload)
        device = device_gate(cell["chips"], args.rehearse)
        say(f"{args.workload} seed={args.seed} seconds={args.seconds} "
            f"trace={args.trace} on {device}")
        os.makedirs(OUT_DIR, exist_ok=True)
        result, detail = run_cell(cell, args, device)
    except BenchmarkError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    except ImportError as e:
        print(f"benchmarks/run.py: the program is not here: {e!r}",
              file=sys.stderr)
        return 2
    for f in detail["failures"]:
        say(f"FAILED: {f}")
    print(json.dumps(detail))
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    faulthandler.enable()
    # a hang says where: every thread's stack, then a non-zero exit, inside
    # the 1200 s the first (compiling) run of a cell may take
    faulthandler.dump_traceback_later(1150, exit=True)
    sys.exit(main())
