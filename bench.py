"""Driver benchmark — one JSON line per BASELINE.md config, headline last.

Configs (BASELINE.md "Baselines to measure"):
  1. filter      — single filter+project query (SimpleFilterSingleQueryPerformance shape)
  2. groupby     — lengthBatch(10000) sum/avg group-by over 1M keys  ◄ HEADLINE (printed last)
  3. distinct    — 60-sec sliding time window, exact distinctCount
  4. pattern     — every A -> B[b.val == a.val] within 5 sec (batched NFA)
  5. join        — stream-stream equi join over two length(100k) windows
  6. overload    — bounded-ingress drop.old under a 10x producer/consumer
                   mismatch: sustained delivery rate + exact drop counts
  7. upgrade     — blue-green hot-swap under sustained traffic: cutover
                   pause ms + exact conservation (sent == delivered)

Events are synthesized host-side as pre-encoded columnar batches (dictionary
interning amortizes in steady state) and pushed through each query's jitted
step on the default device (the TPU where one is attached; CPU elsewhere —
every line names its `backend` and `device_kind`).
Throughput is pipelined (async dispatch, one barrier per window, best of 3:
a per-step block measures the host↔device sync, not the engine; rounds 1–4,
on another runner, paid ~80 ms per sync — not re-measured on a directly
attached chip). p99 is synchronous per-step.

Each config's JSON line carries three numbers (VERDICT r02 item 8):
  value                 — pipelined throughput through the jitted step
                          (async dispatch, one barrier per window, best of 3)
  e2e_events_per_sec    — the PUBLIC path: InputHandler.send_columns(numpy
                          columns; string symbols as Python objects from a
                          pooled universe, interned per value by the native
                          encoder) → junction dispatch → jitted step →
                          async columnar callback (ColumnarBlock — the
                          batch-level form of the reference's Event[]
                          callback, StreamCallback.java:38). The clock
                          includes runtime.drain(): every output event has
                          reached the callback before the elapsed is read.
                          Each batch pays a device→host readback (pipelined
                          by the async decoder);
                          e2e_colocated_events_per_sec is the same
                          measurement on the CPU backend in a fresh
                          subprocess — host path vs device path, separated.
  e2e_rows_events_per_sec — secondary: the same path fed with per-row
                          Python tuples (send_batch) and per-Event
                          callbacks — the row-at-a-time public API
  device_step_ms        — per-step time of the state-chained pipelined loop
                          (the chain serializes device execution, dispatch
                          overlaps: device-bound to first order), vs
  p99_batch_latency_ms  — synchronous single-step round trip, which
                          includes the host↔device sync.

vs_baseline: BASELINE.json `published` is empty and no JVM exists in this
image to measure the reference, so each denominator falls back to the
per-config estimates in `_DENOMINATORS` below — per-shape order-of-magnitude
figures for single-JVM CPU Siddhi, chosen HIGH (favoring the reference) so
ratios are conservative. Measured numbers added to BASELINE.json under
published[<metric key>] take precedence.

WATCHDOG DISCIPLINE (round 6 — BENCH_r05 produced ZERO numbers because the
first config hung >=900 s under the TPU driver): the bench can no longer go
dark. Every config runs in its own subprocess under a hard parent-side
deadline; the child emits `#partial {json}` checkpoints after each measured
sub-metric AND arms a best-effort SIGALRM, so when the parent kills a wedged
config it still merges the partials into a numeric JSON line tagged
"partial": true. A `--max-seconds` total budget bounds the whole run;
heartbeat progress lines go to stderr every 10 s. Steady-state numbers
exclude compilation: e2e runtimes start with AOT warmup
(SiddhiAppRuntime.warmup — the shape-bucket ladder compiles before the
clock starts).

Usage: python bench.py [config ...] [--max-seconds=N] [--config-seconds=N]
       (default: all five configs, headline last; N defaults 850 / 240)
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

BATCH = 8192
#: e2e micro-batch: the public path amortizes per-batch costs (one device
#: dispatch + one device→host readback per batch) over more events, so e2e
#: uses a larger compiled batch than the device measure. (Rounds 1–4
#: measured the readback at ~100 ms per batch on another runner; not
#: re-measured on a directly attached chip.)
#: BACKEND-AWARE (round 6): on the CPU backend XLA's compile time for a
#: 128k-lane aggregation step grows into minutes on small hosts — CPU runs
#: use 16384 so every config fits its watchdog budget. SIDDHI_E2E_BATCH
#: overrides either way; resolved lazily in the child (after the backend is
#: forced) via _resolve_e2e_batch.
E2E_BATCH = int(os.environ.get("SIDDHI_E2E_BATCH", 0)) or None


def _is_cpu() -> bool:
    # importing siddhi_tpu FIRST matters: its __init__ disables XLA:CPU
    # async dispatch (pure_callback deadlock guard), and the flag only
    # takes effect if set before jax creates its CPU client — which
    # jax.default_backend() does
    import siddhi_tpu  # noqa: F401
    import jax
    return jax.default_backend() == "cpu"


def _resolve_e2e_batch() -> int:
    global E2E_BATCH
    if E2E_BATCH is None:
        E2E_BATCH = 16384 if _is_cpu() else 131072
    return E2E_BATCH
WARMUP = 3
STEPS = 40
LAT_STEPS = 50
RNG_SEED = 7
#: --e2e-only: skip device measures, print only the e2e number (used by the
#: parent process to collect the co-located CPU variant)
E2E_ONLY = "--e2e-only" in sys.argv
T0 = time.monotonic()


def _flag(name: str, default: float) -> float:
    for a in sys.argv[1:]:
        if a.startswith(f"--{name}="):
            return float(a.split("=", 1)[1])
    return default


#: total wall budget for the whole run (parent mode) — chosen under the
#: driver's observed 900 s per-command ceiling
MAX_SECONDS = _flag("max-seconds", 850.0)
#: per-config watchdog: the parent kills a config subprocess at this bound
#: (clamped to the remaining total budget) and emits its partials
CONFIG_SECONDS = _flag("config-seconds", 240.0)

#: child-mode partial results: every measured sub-metric lands here AND is
#: echoed as a `#partial {json}` stdout line, so a killed child still
#: yields numbers for whatever finished
PARTIAL: dict = {}
_PHASE = ["init"]


def _phase(name: str) -> None:
    _PHASE[0] = name
    print(f"[bench] t={time.monotonic() - T0:.0f}s phase={name}",
          file=sys.stderr, flush=True)


def _partial(res: dict) -> None:
    PARTIAL.update(res)
    print("#partial " + json.dumps(res), flush=True)


class BenchTimeout(Exception):
    """Raised by the child's SIGALRM handler (best-effort in-process bound;
    the parent's kill is the hard one)."""


def _arm_child_watchdog(seconds: float) -> None:
    """SIGALRM -> BenchTimeout, plus a stderr heartbeat thread. The alarm
    fires only when the main thread executes Python bytecode — a hang
    inside one XLA compile outlives it, which is why the parent holds the
    authoritative deadline."""
    import signal
    if seconds > 0 and hasattr(signal, "SIGALRM"):
        def _on_alarm(_sig, _frm):
            raise BenchTimeout(f"alarm after {seconds:.0f}s")
        signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(max(int(seconds), 1))

    def _beat():
        while True:
            time.sleep(10)
            print(f"[bench] t={time.monotonic() - T0:.0f}s "
                  f"phase={_PHASE[0]} alive", file=sys.stderr, flush=True)

    threading.Thread(target=_beat, daemon=True, name="bench-heartbeat").start()


#: per-config single-JVM CPU estimates (events/sec), used when BASELINE.json
#: publishes no measured number. Basis: the reference's performance-samples
#: print throughput for these shapes on one JVM; per-event costs differ by
#: orders of magnitude across shapes (a filter is one virtual call per event;
#: a window join is a per-event find() against a 100k-event window). Chosen
#: at the HIGH end of plausible for the reference so vs_baseline understates
#: rather than flatters.
_DENOMINATORS = {
    # tight per-event filter loop, no state: millions/sec per core
    "filter_events_per_sec": 5_000_000.0,
    # per-event HashMap aggregation over 1M keys + 10k-batch flushes
    "lengthBatch10k_groupby_1M_keys_events_per_sec": 1_000_000.0,
    # sliding expiry walk + per-value distinct map per event
    "sliding60s_distinctCount_events_per_sec": 500_000.0,
    # per-event NFA pending-list scan with within-expiry
    "pattern_everyAB_within5s_events_per_sec": 500_000.0,
    # per-event find() against the opposite 100k-event window (the
    # reference has no window hash index; its per-event probe walks the
    # window's event chain with a compiled condition)
    "join_100kx100k_events_per_sec": 500_000.0,
    # sustained delivery under 10x overload with a bounded @async buffer:
    # bounded by the injected 2 ms/step consumer stall, not the engine —
    # denominator chosen as the reference's single-JVM ring throughput
    "overload_sustained_events_per_sec": 1_000_000.0,
    # multi-producer binary ingestion through the service surface into a
    # filter -> group-by app: the reference's HTTP/TCP source + Disruptor
    # ring tops out around its single-JVM ring throughput; the per-event
    # path is one mapper call + ring publish per event
    "e2e_ingress_events_per_sec": 1_000_000.0,
    # 256 co-resident queries: every event visits every query's per-event
    # callback chain in the reference, so single-JVM throughput divides by
    # query count; 100k favors the reference for this shape
    "fanout256_events_per_sec": 100_000.0,
    # partition-key sharded pipeline replicas behind the frame router: the
    # reference's comparable deployment is one JVM per partition group
    # behind an external partitioner, bounded by its single-JVM ring rate
    "sharded_e2e_events_per_sec": 1_000_000.0,
    # sustained rate under Poisson attach/detach churn: the reference
    # redeploys the whole app per membership change (stop-the-world), so
    # its sustained number under churn collapses toward redeploy time;
    # denominator matches the fanout shape it churns over
    "churn_sustained_events_per_sec": 100_000.0,
}


def _preflight(app: str) -> dict:
    """Static-analysis overhead per config app: parse, lint (the SL rule
    catalog over the plan graph), and full validate (plan + discard, the
    SIDDHI_LINT=error worst case). One-shot wall times in ms — these land
    in BENCH_*.json so lint cost regressions show up next to throughput."""
    from siddhi_tpu import SiddhiManager, compiler
    from siddhi_tpu.analysis import analyze

    t0 = time.perf_counter()
    parsed = compiler.parse(app)
    parse_ms = (time.perf_counter() - t0) * 1e3
    t1 = time.perf_counter()
    report = analyze(parsed)
    lint_ms = (time.perf_counter() - t1) * 1e3
    t2 = time.perf_counter()
    SiddhiManager().validate_siddhi_app(parsed)
    validate_ms = (time.perf_counter() - t2) * 1e3
    out = {
        "parse_ms": round(parse_ms, 2),
        "lint_ms": round(lint_ms, 2),
        "validate_ms": round(validate_ms, 2),
        "lint_findings": len(report.diagnostics),
    }
    if report.cost is not None:
        # advisory prediction (analysis/cost.py) riding next to the
        # measurement; bench_compare ignores these when diffing rounds
        out["cost_predicted_state_bytes"] = \
            report.cost["predicted_state_bytes"]
        out["cost_predicted_compiles"] = report.cost["predicted_compiles"]
    _partial(out)
    return out


def _baseline_for(key: str) -> float:
    fallback = _DENOMINATORS.get(key, 1_000_000.0)
    try:
        with open("BASELINE.json") as f:
            pub = json.load(f).get("published", {})
        return float(pub.get(key, fallback))
    except Exception:
        return fallback


def _measure(run_step, events_per_step: int, metric: str, *,
             warmup: int = WARMUP, steps: int = STEPS) -> dict:
    """run_step(i) -> device out; pipelined best-of-3 + synchronous p99.
    Warmup is BOUNDED: it stops early once it has burned half the child's
    remaining alarm budget (first-compile pathologies then surface as a
    `warmup_truncated` partial instead of a silent hang)."""
    import jax

    if _is_cpu():
        # CPU hosts pay 10-100x per device step: a quarter of the step
        # count still averages over enough steps to be stable, and keeps
        # each config inside its fair-share slice of the outer deadline
        steps = max(8, steps // 4)
    _phase(f"{metric}:warmup")
    w0 = time.monotonic()
    w_budget = max(CONFIG_SECONDS / 2, 30.0)
    done = 0
    out = None
    for i in range(warmup):
        out = run_step(i)
        jax.block_until_ready(out)
        done += 1
        if time.monotonic() - w0 > w_budget:
            _partial({"warmup_truncated": done})
            break
    _partial({"warmup_s": round(time.monotonic() - w0, 2)})
    _phase(f"{metric}:throughput")

    events_per_sec = 0.0
    for _rep in range(3):
        t0 = time.perf_counter()
        for i in range(steps):
            out = run_step(i)
        jax.block_until_ready(out)
        elapsed = time.perf_counter() - t0
        events_per_sec = max(events_per_sec, events_per_step * steps / elapsed)

    _phase(f"{metric}:p99")
    lat = []
    n_lat = LAT_STEPS
    for i in range(LAT_STEPS):
        t0 = time.perf_counter()
        out = run_step(i)
        jax.block_until_ready(out)
        lat.append(time.perf_counter() - t0)
        if i == 0 and lat[0] > 0.2:
            # slow-host guard: 50 synchronous 500 ms steps would eat the
            # watchdog budget; a >=10-sample p99 still bounds the tail
            n_lat = max(10, LAT_STEPS // 5)
        if i + 1 >= n_lat:
            break
    lat_arr = np.array(lat)
    p99_ms = float(np.percentile(lat_arr, 99) * 1e3)
    p50_ms = float(np.percentile(lat_arr, 50) * 1e3)

    baseline = _baseline_for(metric)
    res = {
        "metric": metric,
        "value": round(events_per_sec, 1),
        "unit": "events/sec",
        "vs_baseline": round(events_per_sec / baseline, 3),
        "device_step_ms": round(events_per_step * 1e3 / events_per_sec, 4),
        "p99_batch_latency_ms": round(p99_ms, 3),
        # first-class percentile fields for every config; e2e runs
        # overwrite them with true ingest→delivery numbers from the
        # telemetry histograms (_e2e_latency_fields)
        "p50_latency_ms": round(p50_ms, 3),
        "p99_latency_ms": round(p99_ms, 3),
    }
    _partial(res)
    return res


def _e2e_latency_fields(rt) -> dict:
    """p50/p99 end-to-end batch latency (mint-at-ingress → delivery end)
    from the always-on telemetry stage histograms, merged across streams."""
    from siddhi_tpu.telemetry.metrics import N_BUCKETS, quantile_from_buckets
    tele = getattr(rt.ctx, "telemetry", None)
    if tele is None or not tele.on:
        return {}
    buckets = [0] * N_BUCKETS
    count = 0
    for (_stream, stage), hist in tele.stage_hist.samples():
        if stage != "e2e":
            continue
        b, c, _ = hist.snapshot()
        for i in range(N_BUCKETS):
            buckets[i] += b[i]
        count += c
    if not count:
        return {}
    return {
        "p50_latency_ms":
            round(quantile_from_buckets(buckets, count, 0.5) / 1e6, 3),
        "p99_latency_ms":
            round(quantile_from_buckets(buckets, count, 0.99) / 1e6, 3),
    }


#: p50/p99 of the most recent _measure_e2e run (merged into the config's
#: result dict by each caller)
_E2E_LAT: dict = {}


def _warmup_or_die(rt, buckets) -> None:
    """AOT-warm `rt` at `buckets`; any step that does not compile ends the
    config here, named, rather than on a feeder thread mid-measurement."""
    failed = rt.warmup(buckets).failures
    if failed:
        raise RuntimeError("AOT warm-up failed to compile: " + "; ".join(
            f"{name}: {err!r}" for name, err in failed.items()))


def _measure_e2e(rt, out_stream: str, feed_round, events_per_round: int,
                 *, rounds: int = 8, warmup: int = 2,
                 columnar: bool = True) -> float:
    """End-to-end throughput through the PUBLIC ingestion path:
    InputHandler.send_columns (or send_batch for the rows variant) → host
    encode (native C, interning) → junction → jitted step → async callback
    delivery. `columnar=True` subscribes a ColumnarBlock callback (the
    batch-level Event[] analogue); False materializes per-row Event objects.
    The clock stops at drain() — every produced event has been decoded and
    delivered to the callback before elapsed is read, so async decode
    pipelines the device→host round trips but cannot hide undone work."""
    # bench-time chaos soak: SIDDHI_FAULT_SPEC (e.g. "sink:p=0.01,seed=7")
    # injects seeded faults into the runtime's transports so sustained
    # throughput is measured THROUGH the retry/dead-letter paths, not only
    # on the sunny day (siddhi_tpu/util/faults.py documents the grammar)
    fault_plans = {}
    if os.environ.get("SIDDHI_FAULT_SPEC"):
        from siddhi_tpu.util.faults import apply_fault_spec
        fault_plans = apply_fault_spec(rt)
    if _is_cpu():
        rounds = max(2, rounds // 2)  # see _measure's CPU shrink
    n_out = [0]
    if columnar:
        rt.add_callback(out_stream, lambda blk: n_out.__setitem__(
            0, n_out[0] + blk.count), columnar=True)
    else:
        rt.add_callback(out_stream, lambda evs: n_out.__setitem__(
            0, n_out[0] + len(evs)))
    _phase(f"e2e:{out_stream}:aot_warmup")
    t_w = time.monotonic()
    rt.start()
    # AOT-warm the FULL-WIDTH bucket only: the e2e feed sends exact
    # full-capacity batches (no auto-flush, no heartbeats), so batch_size
    # is the single shape this run dispatches — warming more rungs of a
    # 1M-group aggregation step repeats its dominant (group-capacity)
    # compile cost for shapes never hit
    caps = {j.batch_size for j in rt.junctions.values()}
    _warmup_or_die(rt, tuple(sorted(caps)))
    _partial({"aot_warmup_s": round(time.monotonic() - t_w, 2)})
    _phase(f"e2e:{out_stream}:feed")
    for r in range(warmup):
        feed_round(r)
    rt.drain()
    best = 0.0
    r0 = warmup
    for _rep in range(3):  # best-of-3 (chosen in rounds 1–4 against drift)
        t0 = time.perf_counter()
        for r in range(r0, r0 + rounds):
            feed_round(r)
        rt.drain()
        elapsed = time.perf_counter() - t0
        r0 += rounds
        best = max(best, events_per_round * rounds / elapsed)
    _E2E_LAT.clear()
    _E2E_LAT.update(_e2e_latency_fields(rt))
    rt.shutdown()
    if fault_plans:
        _partial({"fault_injection": {
            t: {"calls": p.calls, "fired": p.fired}
            for t, p in fault_plans.items()}})
    assert n_out[0] > 0, "e2e run produced no output — not a valid measure"
    return best


def _measure_autoflush_p99(app: str, *, rate_hz: float = 1000.0,
                           seconds: float = 2.0) -> float:
    """p99 send→callback latency at a LOW event rate with auto-flush: the
    caller never calls flush(); the runtime's wall-clock flusher must bound
    staged latency (target < 50 ms co-located)."""
    from siddhi_tpu import SiddhiManager

    rt = SiddhiManager().create_siddhi_app_runtime(
        app, batch_size=256, auto_flush_ms=10, aot_warmup=True)
    lat: list = []
    pend: dict = {}

    def cb(evs):
        t = time.perf_counter()
        for e in evs:
            s = pend.pop(e.data[1], None)
            if s is not None:
                lat.append((t - s) * 1e3)

    rt.add_callback(next(
        ln.split("insert into ")[1].split(";")[0].strip()
        for ln in app.splitlines() if "insert into" in ln), cb)
    rt.start()
    h = rt.get_input_handler("TradeStream")
    for i in range(5):  # warm the partial-batch compile out of the measure
        h.send(("WARM", 1e9 + i, 1))
        time.sleep(0.05)
    v = 1.0
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        pend[v] = time.perf_counter()
        h.send(("S1", v, 1))
        v += 1.0
        time.sleep(1.0 / rate_hz)
    time.sleep(0.2)
    rt.shutdown()
    if not lat:
        return float("inf")
    lat.sort()
    return round(lat[min(int(len(lat) * 0.99), len(lat) - 1)], 2)


def _trade_rows(n_rounds: int, n_keys: int, *, price_hi: float = 100.0,
                n: int = BATCH):
    """Host python rows (string symbols) for the e2e rows-path variant."""
    rng = np.random.default_rng(RNG_SEED + 1)
    rounds = []
    for _ in range(n_rounds):
        ks = rng.integers(1, n_keys + 1, n)
        ps = rng.uniform(1.0, price_hi, n)
        vs = rng.integers(1, 1000, n)
        rounds.append([(f"S{int(k)}", float(p), int(v))
                       for k, p, v in zip(ks, ps, vs)])
    return rounds


def _trade_cols(n_rounds: int, n_keys: int, *, price_hi: float = 100.0,
                n: int = BATCH):
    """Columnar public-path feed: numpy columns per round. Symbols are
    Python string objects drawn from a pooled universe — the realistic
    producer shape (market-data handlers intern their symbol strings), and
    what the native encoder's pointer-identity memo is built for."""
    rng = np.random.default_rng(RNG_SEED + 1)
    pool = np.array([f"S{i}" for i in range(1, n_keys + 1)], dtype=object)
    rounds = []
    for _ in range(n_rounds):
        ks = rng.integers(0, n_keys, n)
        rounds.append({
            "symbol": pool[ks],
            "price": rng.uniform(1.0, price_hi, n),
            "volume": rng.integers(1, 1000, n),
        })
    return rounds


def _trade_batches(n: int, n_keys: int, *, ms_per_event: int = 0,
                   price_hi: float = 100.0):
    from siddhi_tpu.core.event import EventBatch

    rng = np.random.default_rng(RNG_SEED)
    batches, ts0 = [], 1
    for _ in range(n):
        if ms_per_event:
            ts = np.arange(ts0, ts0 + BATCH * ms_per_event, ms_per_event,
                           dtype=np.int64)
            ts0 += BATCH * ms_per_event
        else:
            ts = np.arange(ts0, ts0 + BATCH, dtype=np.int64)
            ts0 += BATCH
        cols = {
            # pre-encoded dictionary codes (1..n_keys); code 0 is null
            "symbol": rng.integers(1, n_keys + 1, BATCH, dtype=np.int32),
            "price": rng.uniform(1.0, price_hi, BATCH).astype(np.float32),
            "volume": rng.integers(1, 1000, BATCH, dtype=np.int64),
        }
        batches.append(EventBatch.from_numpy(ts, cols, BATCH))
    return batches, ts0


# --------------------------------------------------------------------- configs


def bench_filter() -> dict:
    """BASELINE config 1: single filter+project (reference:
    SimpleFilterSingleQueryPerformance.java:40-52, `700 > price`)."""
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager

    app = """
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'bench')
    from TradeStream[700.0 > price]
    select symbol, price
    insert into OutStream;
    """
    if E2E_ONLY:
        res = {"metric": "filter_events_per_sec"}
    else:
        rt = SiddhiManager().create_siddhi_app_runtime(app, batch_size=BATCH)
        qr = rt.query_runtimes["bench"]
        batches, ts_end = _trade_batches(8, 1000, price_hi=1000.0)
        state = [qr.state]

        def run(i):
            state[0], out = qr._step(state[0], batches[i % len(batches)],
                                     jnp.int64(ts_end))
            return out

        res = _measure(run, BATCH, "filter_events_per_sec")

    rt2 = SiddhiManager().create_siddhi_app_runtime(
        app, batch_size=E2E_BATCH, async_callbacks=True)
    cols = _trade_cols(4, 1000, price_hi=1000.0, n=E2E_BATCH)
    h = rt2.get_input_handler("TradeStream")

    def feed(r):
        h.send_columns(cols[r % len(cols)])
        rt2.flush()

    res["e2e_events_per_sec"] = round(
        _measure_e2e(rt2, "OutStream", feed, E2E_BATCH), 1)
    res.update(_E2E_LAT)
    _partial({"e2e_events_per_sec": res["e2e_events_per_sec"], **_E2E_LAT})

    # auto-flush latency at LOW rate (1k ev/s, no flush() from the caller):
    # the wall-clock flusher bounds staged latency (VERDICT r04 item 5;
    # reference role: the Disruptor's immediate consumption)
    _phase("filter:autoflush_p99")
    res["p99_autoflush_latency_ms"] = _measure_autoflush_p99(app)
    _partial({"p99_autoflush_latency_ms": res["p99_autoflush_latency_ms"]})

    if not E2E_ONLY:  # secondary: row-at-a-time public API
        rt3 = SiddhiManager().create_siddhi_app_runtime(
            app, batch_size=E2E_BATCH, async_callbacks=True)
        rows = _trade_rows(4, 1000, price_hi=1000.0, n=E2E_BATCH)
        h3 = rt3.get_input_handler("TradeStream")

        def feed_rows(r):
            h3.send_batch(rows[r % len(rows)])
            rt3.flush()

        res["e2e_rows_events_per_sec"] = round(
            _measure_e2e(rt3, "OutStream", feed_rows, E2E_BATCH,
                         columnar=False, rounds=4), 1)
        _partial({"e2e_rows_events_per_sec": res["e2e_rows_events_per_sec"]})
        res.update(_preflight(app))
    return res


def bench_groupby() -> dict:
    """BASELINE config 2 (headline): lengthBatch(10000) sum/avg group-by, 1M keys."""
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager

    app = """
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'bench')
    from TradeStream#window.lengthBatch(10000)
    select symbol, sum(price) as total, avg(price) as avgPrice
    group by symbol
    insert into SummaryStream;
    """
    if E2E_ONLY:
        res = {"metric": "lengthBatch10k_groupby_1M_keys_events_per_sec"}
    else:
        rt = SiddhiManager().create_siddhi_app_runtime(
            app, batch_size=BATCH, group_capacity=1 << 20)
        qr = rt.query_runtimes["bench"]
        batches, ts_end = _trade_batches(8, 1_000_000)
        state = [qr.state]

        def run(i):
            state[0], out = qr._step(state[0], batches[i % len(batches)],
                                     jnp.int64(ts_end))
            return out

        res = _measure(run, BATCH,
                       "lengthBatch10k_groupby_1M_keys_events_per_sec")

    rt2 = SiddhiManager().create_siddhi_app_runtime(
        app, batch_size=E2E_BATCH, group_capacity=1 << 20,
        async_callbacks=True)
    cols = _trade_cols(4, 1_000_000, n=E2E_BATCH)
    h = rt2.get_input_handler("TradeStream")

    def feed(r):
        h.send_columns(cols[r % len(cols)])
        rt2.flush()

    res["e2e_events_per_sec"] = round(
        _measure_e2e(rt2, "SummaryStream", feed, E2E_BATCH), 1)
    res.update(_E2E_LAT)
    _partial({"e2e_events_per_sec": res["e2e_events_per_sec"], **_E2E_LAT})
    if not E2E_ONLY:
        res.update(_preflight(app))
    return res


def bench_distinct() -> dict:
    """BASELINE config 3: 60-sec sliding time window, exact distinctCount.
    ~1 ms event spacing -> the window holds ~60k events in steady state."""
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager

    app = """
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'bench')
    from TradeStream#window.time(60 sec)
    select distinctCount(symbol) as distinctSymbols
    insert into OutStream;
    """
    if E2E_ONLY:
        res = {"metric": "sliding60s_distinctCount_events_per_sec"}
        return _distinct_e2e(app, res)
    # lifetime-unique values bounded (100k) well under the 1M pair capacity
    rt = SiddhiManager().create_siddhi_app_runtime(
        app, batch_size=BATCH, group_capacity=1 << 20)
    qr = rt.query_runtimes["bench"]
    # timestamps must keep advancing monotonically across ALL phases
    # (warmup, 3 throughput reps, latency loop) or the 60 s window drains
    # and the watermark regresses. Build every step's batch host-side:
    # feeding device-computed arrays (e.g. a device-side ts shift) into a
    # step serialized async dispatch in rounds 1–4 (a per-step artifact on
    # that runner; not re-measured), while host-built batches pipeline —
    # and host batches are what the real ingestion path produces.
    n_steps = WARMUP + 3 * STEPS + LAT_STEPS + 8
    batches, _ = _trade_batches(n_steps, 100_000, ms_per_event=1)
    state = [qr.state]
    step_no = [0]

    def run(_i):
        k = step_no[0]
        step_no[0] += 1
        b = batches[k]
        now = jnp.int64((k + 1) * BATCH)
        state[0], out = qr._step(state[0], b, now)
        return out

    res = _measure(run, BATCH, "sliding60s_distinctCount_events_per_sec")
    return _distinct_e2e(app, res)


def _distinct_e2e(app: str, res: dict) -> dict:
    from siddhi_tpu import SiddhiManager

    rt2 = SiddhiManager().create_siddhi_app_runtime(
        app, batch_size=E2E_BATCH, group_capacity=1 << 20,
        async_callbacks=True)
    cols = _trade_cols(4, 100_000, n=E2E_BATCH)
    h = rt2.get_input_handler("TradeStream")
    ts_ctr = [1]

    def feed(r):
        t = ts_ctr[0]
        ts_ctr[0] = t + E2E_BATCH
        h.send_columns(cols[r % len(cols)],
                       timestamps=np.arange(t, t + E2E_BATCH,
                                            dtype=np.int64))
        rt2.flush()

    res["e2e_events_per_sec"] = round(
        _measure_e2e(rt2, "OutStream", feed, E2E_BATCH), 1)
    res.update(_E2E_LAT)
    _partial({"e2e_events_per_sec": res["e2e_events_per_sec"], **_E2E_LAT})
    if not E2E_ONLY:
        res.update(_preflight(app))
    return res


def bench_pattern() -> dict:
    """BASELINE config 4: `every a=A -> b=B[b.val == a.val] within 5 sec`.
    Alternating A/B batches; every B consumes exactly one pending A."""
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core import dtypes
    from siddhi_tpu.core.event import EventBatch

    # device NFA time is sub-ms, so per-dispatch overhead dominates at small
    # batches: run full-width batches with pending capacity to match
    # device NFA width. On CPU both the compile and the per-step cost of
    # the 4x-pending NFA grow with width — a narrower batch keeps the
    # config inside its watchdog budget on small hosts (same engine path)
    pb = BATCH if not _is_cpu() else 512
    app = """
    define stream StreamA (val int);
    define stream StreamB (val int);
    @info(name = 'bench')
    from every a=StreamA -> b=StreamB[b.val == a.val] within 5 sec
    select a.val as aVal, b.val as bVal
    insert into OutStream;
    """
    if E2E_ONLY:
        res = {"metric": "pattern_everyAB_within5s_events_per_sec"}
    else:
        prev_cap = dtypes.config.pattern_pending_capacity
        dtypes.config.pattern_pending_capacity = 4 * pb
        try:
            rt = SiddhiManager().create_siddhi_app_runtime(app, batch_size=pb)
            qr = rt.query_runtimes["bench"]
        finally:
            dtypes.config.pattern_pending_capacity = prev_cap

        n_cycles = 4
        ab = []
        ts0 = 1
        for k in range(n_cycles):
            vals = np.arange(k * pb, (k + 1) * pb, dtype=np.int32)
            ts_a = np.arange(ts0, ts0 + pb, dtype=np.int64)
            a = EventBatch.from_numpy(ts_a, {"val": vals}, pb)
            ts_b = ts_a + pb
            b = EventBatch.from_numpy(ts_b, {"val": vals}, pb)
            ts0 += 2 * pb
            ab.append((a, b, ts0 - 1))
        state = [qr.state]

        def run(i):
            a, b, now = ab[i % n_cycles]
            state[0], _ = qr._steps["StreamA"](state[0], a, jnp.int64(now - pb))
            state[0], out = qr._steps["StreamB"](state[0], b, jnp.int64(now))
            return out

        res = _measure(run, 2 * pb, "pattern_everyAB_within5s_events_per_sec")

    # e2e batch: amortizes the per-batch dispatch + readback; CPU shrinks
    # with the device width (cheaper steps)
    eb = 32768 if not _is_cpu() else 2048
    prev_cap = dtypes.config.pattern_pending_capacity
    dtypes.config.pattern_pending_capacity = 4 * eb
    try:
        rt2 = SiddhiManager().create_siddhi_app_runtime(
            app, batch_size=eb, async_callbacks=True)
    finally:
        dtypes.config.pattern_pending_capacity = prev_cap
    ha = rt2.get_input_handler("StreamA")
    hb = rt2.get_input_handler("StreamB")
    val_ctr = [0]

    def feed(r):
        v0 = val_ctr[0]
        val_ctr[0] += eb
        vals = np.arange(v0, v0 + eb, dtype=np.int32)
        ha.send_columns({"val": vals})
        rt2.flush()
        hb.send_columns({"val": vals})
        rt2.flush()

    res["e2e_events_per_sec"] = round(
        _measure_e2e(rt2, "OutStream", feed, 2 * eb), 1)
    res.update(_E2E_LAT)
    _partial({"e2e_events_per_sec": res["e2e_events_per_sec"], **_E2E_LAT})
    if not E2E_ONLY:
        res.update(_preflight(app))
    return res


def bench_join() -> dict:
    """BASELINE config 5: equi join over two length(100000) windows; keys
    uniform over 100k so each probe matches ~1 build row."""
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.event import EventBatch

    app = """
    define stream LeftStream (k int, v double);
    define stream RightStream (k int, v double);
    @info(name = 'bench')
    from LeftStream#window.length(100000) as a
    join RightStream#window.length(100000) as b
    on a.k == b.k
    select a.k as k, a.v as lv, b.v as rv
    insert into OutStream;
    """
    if E2E_ONLY:
        res = {"metric": "join_100kx100k_events_per_sec"}
    else:
        rt = SiddhiManager().create_siddhi_app_runtime(app, batch_size=BATCH)
        qr = rt.query_runtimes["bench"]

        rng = np.random.default_rng(RNG_SEED)
        n_distinct = 8
        lr = []
        ts0 = 1
        for _ in range(n_distinct):
            ts = np.arange(ts0, ts0 + BATCH, dtype=np.int64)
            ts0 += BATCH
            mk = lambda: {"k": rng.integers(1, 100_001, BATCH, dtype=np.int32),
                          "v": rng.uniform(1.0, 100.0, BATCH).astype(np.float32)}
            lr.append((EventBatch.from_numpy(ts, mk(), BATCH),
                       EventBatch.from_numpy(ts, mk(), BATCH)))
        state = [qr.state]

        def run(i):
            l, r = lr[i % n_distinct]
            now = jnp.int64(ts0)
            state[0], _, _ = qr._step_left(state[0], l, now, None)
            state[0], out, _ = qr._step_right(state[0], r, now, None)
            return out

        res = _measure(run, 2 * BATCH, "join_100kx100k_events_per_sec")

    # join e2e stays at the device batch: the join's OUTPUT block scales
    # with pair_cap_factor x B, so larger input batches inflate the per-batch
    # readback superlinearly (measured: 8192 beats 16k/32k through the wire)
    jb = BATCH
    rt2 = SiddhiManager().create_siddhi_app_runtime(
        app, batch_size=jb, async_callbacks=True)
    rng2 = np.random.default_rng(RNG_SEED + 1)
    rounds = []
    for _ in range(4):
        mk = lambda: {"k": rng2.integers(1, 100_001, jb).astype(np.int32),
                      "v": rng2.uniform(1.0, 100.0, jb)}
        rounds.append((mk(), mk()))
    hl = rt2.get_input_handler("LeftStream")
    hr = rt2.get_input_handler("RightStream")

    def feed(r):
        lcols, rcols = rounds[r % len(rounds)]
        hl.send_columns(lcols)
        rt2.flush()
        hr.send_columns(rcols)
        rt2.flush()

    res["e2e_events_per_sec"] = round(
        _measure_e2e(rt2, "OutStream", feed, 2 * jb), 1)
    res.update(_E2E_LAT)
    _partial({"e2e_events_per_sec": res["e2e_events_per_sec"], **_E2E_LAT})
    if not E2E_ONLY:
        res.update(_preflight(app))
    return res


def bench_overload() -> dict:
    """Satellite config: sustained throughput UNDER overload — a producer
    running ~10x faster than a deliberately slowed consumer into a bounded
    `@Async(overflow.policy='drop.old')` stream. Reports the delivered
    (sustained) rate plus exact drop counts, and asserts conservation:
    every sent event was delivered, dropped-by-policy, or counted at
    shutdown — bounded ingress may shed load but never silently."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.util.faults import FaultPlan, inject

    res = {"metric": "overload_sustained_events_per_sec"}
    if E2E_ONLY:  # no second CPU pass for this config
        return res
    app = """
    @app:name('Overload')
    @Async(buffer.size='256', overflow.policy='drop.old', max.staged='1024')
    define stream TradeStream (v long);
    @info(name = 'bench')
    from TradeStream select v insert into OutStream;
    """
    rt = SiddhiManager().create_siddhi_app_runtime(app)
    delivered = [0]
    rt.add_callback("OutStream", lambda blk: delivered.__setitem__(
        0, delivered[0] + blk.count), columnar=True)
    # the slow consumer: every query step stalls 2 ms (seeded, always due),
    # capping consumption at ~128k ev/s while the producer pushes millions
    qr = rt.query_runtimes["bench"]
    inject(qr, "on_batch", FaultPlan(p=1.0, seed=RNG_SEED, slow_s=0.002))
    rt.start()
    h = rt.get_input_handler("TradeStream")
    rows = [(int(i),) for i in range(256)]

    _phase("overload:warmup")
    h.send_batch(rows)
    t0 = time.monotonic()
    while delivered[0] == 0 and time.monotonic() - t0 < CONFIG_SECONDS / 2:
        time.sleep(0.01)  # first batch through = compile done
    sent = 256

    _phase("overload:feed")
    t0 = time.perf_counter()
    t_end = t0 + 4.0
    while time.perf_counter() < t_end:
        h.send_batch(rows)
        sent += 256
    rt.flush()
    rt.shutdown()  # drains whatever is still staged
    elapsed = time.perf_counter() - t0

    rep = rt.statistics_report()
    drops = rep["ingress_dropped"].get("TradeStream", {})
    dropped = sum(drops.values())
    discarded = rep["recovery"]["shutdown_discarded"]
    res.update({
        "value": round(delivered[0] / elapsed, 1),
        "unit": "events/sec",
        "vs_baseline": round(
            delivered[0] / elapsed
            / _baseline_for("overload_sustained_events_per_sec"), 3),
        "sent": sent,
        "dropped": dropped,
        "drop_rate": round(dropped / max(sent, 1), 4),
        "queue_hwm": rep["backpressure"]["queue_hwm"].get("TradeStream", 0),
        "conservation_ok":
            delivered[0] + dropped + discarded == sent,
    })
    _partial(res)
    res.update(_preflight(app))
    return res


def bench_disorder() -> dict:
    """Satellite config: out-of-order ingress through the @app:eventTime
    gate (core/event_time.py). A seeded bounded-disorder permutation (the
    shuffled-replay oracle's model: displacement < allowed.lateness) feeds
    the gate, with a deliberate 1-in-128 straggler BEYOND the budget.
    Reports the sustained gated rate, the displaced-row share, exact late
    diversions (must equal the injected stragglers — zero silent drops),
    and the gate's conservation identity."""
    import random as _random

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.upgrade import _bounded_shuffle

    res = {"metric": "disorder_gated_events_per_sec"}
    if E2E_ONLY:  # host-side gate: no second CPU pass
        return res
    app = """
    @app:name('Disorder')
    @app:eventTime(timestamp='ts', allowed.lateness='50')
    define stream TradeStream (ts long, v long);
    @info(name = 'bench')
    from TradeStream select ts, v insert into OutStream;
    """
    rt = SiddhiManager().create_siddhi_app_runtime(app)
    delivered = [0]
    rt.add_callback("OutStream", lambda blk: delivered.__setitem__(
        0, delivered[0] + blk.count), columnar=True)
    rt.start()
    h = rt.get_input_handler("TradeStream")

    # sensor-fleet shape: 16 rows per 10 ms event-time tick (so per-ts
    # delivery groups stay batch-sized), displaced by the oracle's bounded
    # shuffle; epoch-ms base keeps the telemetry plausibility window open
    epoch = 1_700_000_000_000
    n_pre, per_tick, batch = 8192, 16, 256
    ordered = [("S", epoch + (i // per_tick) * 10,
                (epoch + (i // per_tick) * 10, i)) for i in range(n_pre)]
    shuffled = _bounded_shuffle(ordered, 50, RNG_SEED)
    displaced = sum(1 for a, b in zip(ordered, shuffled) if a is not b)
    rng = _random.Random(RNG_SEED)
    rows, stragglers = [], 0
    for _sid, ts, row in shuffled:
        if rng.randrange(128) == 0:  # beyond-budget straggler: must divert
            rows.append((ts - 10_000, row[1]))
            stragglers += 1
        else:
            rows.append(row)
    batches = [rows[i:i + batch] for i in range(0, len(rows), batch)]

    _phase("disorder:warmup")
    h.send_batch(batches[0])
    rt.flush()
    sent = len(batches[0])

    _phase("disorder:feed")
    t0 = time.perf_counter()
    t_end = t0 + 4.0
    loops = 0
    while time.perf_counter() < t_end:
        cycle, idx = divmod(loops, len(batches) - 1)
        b = batches[1 + idx]
        if cycle:
            # each recycle re-bases event time above the released horizon
            # so recycled batches don't all classify late
            shift = cycle * 100_000_000
            b = [(ts + shift, v) for ts, v in b]
        h.send_batch(b)
        rt.flush()
        sent += len(b)
        loops += 1
    rt.release_watermarks()
    elapsed = time.perf_counter() - t0
    rt.shutdown()

    wm = rt.statistics_report()["watermarks"]["TradeStream"]
    expected_late = stragglers * max(1, loops // max(1, len(batches) - 1))
    res.update({
        "value": round(delivered[0] / elapsed, 1),
        "unit": "events/sec",
        "vs_baseline": round(
            delivered[0] / elapsed
            / _baseline_for("disorder_gated_events_per_sec"), 3),
        "sent": sent,
        "displaced_share": round(displaced / n_pre, 3),
        "lateness_ms": 50,
        "late_diverted": wm["late"],
        "late_expected_about": expected_late,
        "buffered_after_drain": wm["buffered"],
        "conservation_ok":
            wm["admitted"] == wm["released"] + wm["late"] + wm["buffered"]
            and wm["buffered"] == 0
            and delivered[0] == wm["released"],
    })
    _partial(res)
    res.update(_preflight(app))
    return res


def bench_upgrade() -> dict:
    """Satellite config: blue-green hot-swap (core/upgrade.py) committed in
    the middle of sustained public-path traffic. Reports the source-paused
    (cutover) window — the only span where ingress stalls — and proves exact
    conservation: every event sent before, during, and after the swap is
    delivered exactly once (count AND checksum), by exactly one version."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.state.persistence import InMemoryPersistenceStore

    res = {"metric": "upgrade_cutover_pause_ms"}
    if E2E_ONLY:  # no second CPU pass for this config
        return res
    app_v1 = """
    @app:name('UpgradeBench')
    define stream TradeStream (v long);
    @info(name = 'bench')
    from TradeStream select v insert into OutStream;
    """
    app_v2 = app_v1 + """
    @info(name = 'mirror')
    from TradeStream select v insert into MirrorStream;
    """
    mgr = SiddhiManager()
    mgr.set_persistence_store(InMemoryPersistenceStore())
    rt = mgr.create_siddhi_app_runtime(app_v1, batch_size=1024)
    delivered = [0, 0]  # count, checksum — dupes+losses can't cancel both

    def cb(evs):
        delivered[0] += len(evs)
        delivered[1] += sum(e.data[0] for e in evs)

    rt.add_callback("OutStream", cb)  # migrates with the swap
    rt.start()
    h = rt.get_input_handler("TradeStream")

    _phase("upgrade:warmup")
    h.send_batch([(int(i),) for i in range(1024)])
    rt.flush()
    rt.drain()
    sent, checksum = 1024, sum(range(1024))

    _phase("upgrade:feed")
    summary: dict = {}
    stop = threading.Event()

    def swap():  # mid-stream, against live producer traffic
        time.sleep(0.5)
        summary.update(mgr.upgrade(app_v2))
        stop.set()

    sw = threading.Thread(target=swap, name="bench-upgrade-swap")
    sw.start()
    t0 = time.perf_counter()
    v = sent
    while not stop.is_set() or time.perf_counter() - t0 < 1.5:
        rows = [(int(i),) for i in range(v, v + 256)]
        h.send_batch(rows)  # stale v1 handle: forwards through the redirect
        sent += 256
        checksum += sum(range(v, v + 256))
        v += 256
        mgr.runtimes["UpgradeBench"].flush()
        if time.perf_counter() - t0 > CONFIG_SECONDS / 3:
            break  # watchdog floor — partials still conserve
    sw.join()
    elapsed = time.perf_counter() - t0
    rt2 = mgr.runtimes["UpgradeBench"]
    rt2.drain()
    rt2.shutdown()

    rep = rt2.statistics_report()["upgrade"]
    res.update({
        "value": round(summary.get("cutover_pause_ms", 0.0), 3),
        "unit": "ms",
        "classification": summary.get("classification"),
        "wal_tail_replayed": summary.get("wal_tail_replayed"),
        "sent": sent,
        "delivered": delivered[0],
        "checksum_ok": delivered[1] == checksum,
        "conserved": delivered[0] == sent and delivered[1] == checksum,
        "events_per_sec_through_swap": round((sent - 1024) / elapsed, 1),
        "upgrades": rep["upgrades"],
    })
    _partial(res)
    res.update(_preflight(app_v1))
    return res


def bench_e2e_ingress() -> dict:
    """HEADLINE config: multi-producer SXF1 binary ingestion through the
    service surface (SiddhiService.send_frames — the REST frames endpoint's
    exact code path minus the socket) into an @Async(workers=N) filter →
    lengthBatch group-by app. This engages the full parallel-ingress
    pipeline: lock-free columnar ring claim, GIL-released decode workers,
    ticket-ordered dictionary interning, double-buffered device feeds. The
    per-stage breakdown (decode/intern/h2d/device ms) and overlap ratio
    come from the always-on statistics_report()["ingress_pipeline"]
    section, so a regression in any one stage is visible next to the
    headline rate.

    Swept over superstep depth K in {1, 8, 32} (@app:superstep — one
    lax.scan dispatch + one output fetch per K staged batches,
    core/superstep.py) on fresh runtimes; the headline is the best K and
    each K reports its own p99 so the throughput/latency trade is visible
    in one record."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.io import wire
    from siddhi_tpu.service import SiddhiService

    eb = _resolve_e2e_batch()
    cpu = _is_cpu()
    n_producers = 2 if cpu else 4
    n_workers = 2 if cpu else 4
    n_keys = 10_000

    def app_text(k: int) -> str:
        ss = f"@app:superstep(k='{k}')\n    " if k > 1 else ""
        return f"""
    @app:name('IngressBench')
    {ss}@app:slo(stream='TradeStream', p99.ms='60000')
    @Async(buffer.size='{eb}', workers='{n_workers}')
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'filt')
    from TradeStream[price < 700.0]
    select symbol, price, volume
    insert into MidStream;
    @info(name = 'agg')
    from MidStream#window.lengthBatch(10000)
    select symbol, sum(price) as total, avg(price) as avgPrice
    group by symbol
    insert into SummaryStream;
    """

    app = app_text(1)

    def build_stack(k: int):
        mgr_x = SiddhiManager()
        rt_x = mgr_x.create_siddhi_app_runtime(
            app_text(k), batch_size=eb, group_capacity=1 << 17,
            async_callbacks=True)
        svc_x = SiddhiService(mgr_x)
        n_out_x = [0]
        rt_x.add_callback("SummaryStream", lambda blk: n_out_x.__setitem__(
            0, n_out_x[0] + blk.count), columnar=True)
        rt_x.start()
        _warmup_or_die(rt_x, tuple(sorted(
            {j.batch_size for j in rt_x.junctions.values()})))
        return mgr_x, rt_x, svc_x, n_out_x

    _phase("e2e_ingress:aot_warmup")
    t_w = time.monotonic()
    mgr, rt, svc, n_out = build_stack(1)
    _partial({"aot_warmup_s": round(time.monotonic() - t_w, 2)})

    _phase("e2e_ingress:encode")
    # pre-encoded frame bodies: producer-side dictionary encoding means the
    # server interns per DISTINCT symbol (~n_keys), not per row
    plan = wire.schema_plan(rt.junctions["TradeStream"].definition)
    rng = np.random.default_rng(RNG_SEED + 2)
    bodies = []
    for _p in range(n_producers):
        per = []
        for _ in range(3):
            ks = rng.integers(1, n_keys + 1, eb)
            cols = {
                "symbol": np.array([f"S{int(k)}" for k in ks], dtype=object),
                "price": rng.uniform(1.0, 1000.0, eb),
                "volume": rng.integers(1, 1000, eb),
            }
            per.append(wire.encode_frames(plan, cols, eb))
        bodies.append(per)

    def measure(svc_x, rt_x, rounds: int) -> float:
        def producer(p: int, n_rounds: int, r0: int) -> None:
            per = bodies[p]
            for r in range(n_rounds):
                svc_x.send_frames("IngressBench", "TradeStream",
                                  per[(r0 + r) % len(per)])

        def run_rounds(n_rounds: int, r0: int) -> None:
            threads = [threading.Thread(target=producer,
                                        args=(p, n_rounds, r0),
                                        name=f"bench-producer-{p}")
                       for p in range(n_producers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            rt_x.drain()  # clock stops only after every event is delivered

        run_rounds(2, 0)
        best_x = 0.0
        r0 = 2
        for _rep in range(3):
            t0 = time.perf_counter()
            run_rounds(rounds, r0)
            elapsed = time.perf_counter() - t0
            r0 += rounds
            best_x = max(best_x, n_producers * rounds * eb / elapsed)
        return best_x

    _phase("e2e_ingress:feed")
    rounds = 2 if cpu else 6
    sweep: dict = {}
    best = 0.0
    best_k = 1
    best_pipe: dict = {}
    best_lat: dict = {}
    for k in (1, 8, 32):
        _phase(f"e2e_ingress:feed_k{k}")
        if k == 1:
            mgr_k, rt_k, svc_k, n_out_k = mgr, rt, svc, n_out
        else:
            mgr_k, rt_k, svc_k, n_out_k = build_stack(k)
        # a superstep stages K ring chunks before one scan dispatch, so
        # each rep must push well past K full batches or K=32 would
        # measure only the per-chunk flush fallback
        rounds_k = max(rounds, (3 * k + n_producers - 1) // n_producers)
        rate_k = measure(svc_k, rt_k, rounds_k)
        rep_k = rt_k.statistics_report()  # before shutdown: stop detaches
        pipe_k = rep_k.get("ingress_pipeline", {}).get("TradeStream", {})
        lat_k = _e2e_latency_fields(rt_k)
        rt_k.shutdown()
        assert n_out_k[0] > 0, \
            f"e2e_ingress k={k} produced no output — not a valid measure"
        if k > 1:
            assert pipe_k.get("supersteps_dispatched", 0) > 0, (
                f"superstep k={k} never engaged: "
                f"{pipe_k.get('superstep_decline')}")
        sweep[k] = {"events_per_sec": round(rate_k, 1),
                    "supersteps_dispatched":
                        pipe_k.get("supersteps_dispatched", 0),
                    "superstep_scan_ms":
                        round(pipe_k.get("superstep_scan_ms", 0.0), 1),
                    "superstep_replay_ms":
                        round(pipe_k.get("superstep_replay_ms", 0.0), 1),
                    **lat_k}
        _partial({f"superstep_k{k}_events_per_sec": round(rate_k, 1),
                  f"superstep_k{k}_p99_latency_ms":
                      lat_k.get("p99_latency_ms")})
        if rate_k > best:
            best, best_k, best_pipe, best_lat = rate_k, k, pipe_k, lat_k

    stage = best_pipe.get("stage_ms", {})

    def _mean(name: str):
        cell = stage.get(name) or {}
        return cell.get("mean_ms")

    value = round(best, 1)
    res = {
        "metric": "e2e_ingress_events_per_sec",
        "value": value,
        "unit": "events/sec",
        "vs_baseline": round(
            value / _baseline_for("e2e_ingress_events_per_sec"), 3),
        "e2e_events_per_sec": value,
        "producers": n_producers,
        "ingress_workers": n_workers,
        # superstep sweep: headline is the best K; each K keeps its own
        # p99 so the dispatch-amortization vs batching-delay trade is
        # visible in one record (docs/OBSERVABILITY.md)
        "superstep_best_k": best_k,
        "superstep_k1_events_per_sec": sweep[1]["events_per_sec"],
        "superstep_k8_events_per_sec": sweep[8]["events_per_sec"],
        "superstep_k32_events_per_sec": sweep[32]["events_per_sec"],
        "superstep_k1_p99_latency_ms": sweep[1].get("p99_latency_ms"),
        "superstep_k8_p99_latency_ms": sweep[8].get("p99_latency_ms"),
        "superstep_k32_p99_latency_ms": sweep[32].get("p99_latency_ms"),
        "superstep_sweep": sweep,
        # per-stage means (per worker run / per batch) — the satellite fix
        # replaced bare cumulative totals with {total_ms, batches, mean_ms}
        "decode_mean_ms": _mean("decode"),
        "intern_mean_ms": _mean("intern"),
        "h2d_mean_ms": _mean("h2d"),
        "device_mean_ms": _mean("device"),
        "stage_ms": stage,
        "h2d_overlap_ratio": best_pipe.get("h2d_overlap_ratio"),
        "worker_utilization": best_pipe.get("worker_utilization"),
        "ring_depth_hwm": best_pipe.get("ring_depth_hwm"),
        **best_lat,
    }
    _partial(res)

    # telemetry overhead A/B: identical workload with SIDDHI_TELEMETRY=0
    # (span recording off at AppTelemetry creation — which also disables
    # the @app:slo engine, so the ON side carries tracing + SLO ticks +
    # the flight recorder's rings). Overhead must stay under 5% — the
    # always-on budget from ISSUE 7, inherited by ISSUE 10.
    _phase("e2e_ingress:telemetry_off")
    os.environ["SIDDHI_TELEMETRY"] = "0"
    try:
        # identical workload at the WINNING superstep depth, so the A/B
        # isolates telemetry cost rather than dispatch-mode cost
        mgr_off, rt_off, svc_off, n_off = build_stack(best_k)
        best_off = measure(
            svc_off, rt_off,
            max(rounds, (3 * best_k + n_producers - 1) // n_producers))
        rt_off.shutdown()
        assert n_off[0] > 0
        res["telemetry_off_events_per_sec"] = round(best_off, 1)
        res["telemetry_overhead_pct"] = round(
            max(0.0, (best_off - best) / best_off) * 100.0, 2)
        _partial({"telemetry_off_events_per_sec":
                  res["telemetry_off_events_per_sec"],
                  "telemetry_overhead_pct": res["telemetry_overhead_pct"]})
    finally:
        os.environ.pop("SIDDHI_TELEMETRY", None)
    if not E2E_ONLY:
        res.update(_preflight(app))
    return res


def _bench_failover_leg(reps: int = 2) -> dict:
    """ADVISORY leg of sharded_e2e (bench_compare strips it): the
    multi-host kill-one-host drill timed end to end. Two real
    `python -m siddhi_tpu.service` worker subprocesses, a FrontTier
    router in-process, one worker SIGKILLed under traffic — reports
    detection (heartbeat misses → confirmed dead), takeover (epoch
    commit + WAL-replay adoption + spool drain) and post-failover
    drain wall times, p50/p99 over `reps` drills. Wall-clock numbers
    depend on worker boot and scheduler jitter: trends, not gates."""
    import shutil
    import socket
    import subprocess
    import tempfile
    import urllib.request

    from siddhi_tpu.parallel.front_tier import FrontTier
    from siddhi_tpu.util.faults import kill_host
    from siddhi_tpu.io import wire

    # leave the throughput phases their share of the config budget
    if time.monotonic() - T0 > CONFIG_SECONDS * 0.6:
        return {"skipped": "config time budget exhausted"}

    fo_app = """
    @app:name('FailoverBench')
    @app:shards(n='4', key='symbol')
    define stream TradeStream (symbol string, price double);
    @info(name='agg')
    from TradeStream select symbol, sum(price) as total, count() as n
    group by symbol insert into SummaryStream;
    """

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    repo = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.default_rng(RNG_SEED + 11)
    detect_ms, takeover_ms, drain_ms = [], [], []
    errors = []
    for rep in range(reps):
        tmp = tempfile.mkdtemp(prefix="siddhi-bench-failover-")
        procs = []
        front = None
        try:
            ports = [free_port(), free_port()]
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env["PYTHONPATH"] = repo + os.pathsep + env.get(
                "PYTHONPATH", "")
            env.pop("SIDDHI_FAULT_SPEC", None)  # chaos stays in tests
            for p in ports:
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "siddhi_tpu.service", str(p)],
                    env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            for p in ports:
                boot_by = time.monotonic() + 90
                while time.monotonic() < boot_by:
                    try:
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{p}/health",
                            timeout=2.0).read()
                        break
                    except OSError:
                        time.sleep(0.05)
                else:
                    raise RuntimeError(f"worker :{p} never came up")

            front = FrontTier(
                fo_app, [f"http://127.0.0.1:{p}" for p in ports],
                wal_dir=os.path.join(tmp, "wal"),
                heartbeat_interval_s=0.2, miss_threshold=2,
                max_retries=0, retry_initial_s=0.01, retry_max_s=0.02)
            front.start()
            h = front.get_input_handler("TradeStream")

            def frame(n_rows):
                ks = rng.integers(0, 64, n_rows)
                return [(f"S{int(k)}", float(v) * 0.25)
                        for k, v in zip(ks,
                                        rng.integers(1, 100, n_rows))]

            for _ in range(6):
                h.send_batch(frame(256))
            kill_host(procs[1])
            for _ in range(6):  # spools toward the dead owner
                h.send_batch(frame(256))
            by = time.monotonic() + 30
            while front.failovers_total < 1 and time.monotonic() < by:
                time.sleep(0.02)
            if not front.failover_timings:
                raise RuntimeError("takeover never completed")
            t0 = time.perf_counter()
            front.drain(timeout_s=30)
            drain_ms.append((time.perf_counter() - t0) * 1e3)
            timing = front.failover_timings[0]
            detect_ms.append(float(timing["detect_ms"] or 0.0))
            takeover_ms.append(float(timing["takeover_ms"]))
            cons = front.conservation_report()
            if not cons["conserved"]:
                raise RuntimeError(f"conservation broke: {cons}")
        except Exception as e:  # noqa: BLE001 — advisory leg never fails
            errors.append(f"rep{rep}: {type(e).__name__}: {e}")
        finally:
            if front is not None:
                try:
                    front.shutdown()
                except Exception:  # noqa: BLE001
                    pass
            for pr in procs:
                kill_host(pr)
            shutil.rmtree(tmp, ignore_errors=True)

    if not takeover_ms:
        return {"error": "; ".join(errors) or "no successful drill"}

    def pct(xs, q):
        return round(float(np.percentile(np.asarray(xs), q)), 1)

    out = {
        "reps": len(takeover_ms),
        "detect_ms_p50": pct(detect_ms, 50),
        "detect_ms_p99": pct(detect_ms, 99),
        "takeover_ms_p50": pct(takeover_ms, 50),
        "takeover_ms_p99": pct(takeover_ms, 99),
        "drain_ms_p50": pct(drain_ms, 50),
        "drain_ms_p99": pct(drain_ms, 99),
    }
    if errors:
        out["rep_errors"] = "; ".join(errors)
    return out


def bench_sharded_e2e() -> dict:
    """MULTICHIP config: the sharded execution plane under sustained SXF1
    frame traffic (parallel/shard_plane.py). One app text, shard counts
    swept via SIDDHI_SHARDS ∈ {1, 4, 8}: frames route by partition-key
    hash BEFORE interning, each shard runs a full replica of the
    filter → per-key running-aggregate pipeline. Two phases per count:

      parity      one deterministic single-producer feed; the canonical
                  (sorted-multiset) SHA-256 of the merged SummaryStream
                  output must be IDENTICAL across every shard count AND
                  the unsharded serial engine — prices are multiples of
                  0.25, so per-key partial sums are exact and batching
                  cannot introduce float drift
      throughput  multi-producer frame blast (the e2e_ingress shape),
                  rate = best-of-reps, plus the routing conservation
                  identity sent == Σ delivered+dropped+diverted

    scaling_x4/x8 are the honest same-host ratios vs 1 shard — on a
    single-core CPU container the replicas time-slice one core, so ~1x
    here is expected; the near-linear claim is for multi-device hosts."""
    import hashlib

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.io import wire
    from siddhi_tpu.service import SiddhiService

    eb = _resolve_e2e_batch()
    cpu = _is_cpu()
    n_producers = 2 if cpu else 4
    n_keys = 1000
    app = f"""
    @app:name('ShardedBench')
    @app:shards(n='4', key='symbol')
    @Async(buffer.size='{eb}', workers='2')
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'filt')
    from TradeStream[price < 700.0]
    select symbol, price, volume
    insert into MidStream;
    @info(name = 'agg')
    from MidStream
    select symbol, sum(price) as total, count() as n
    group by symbol
    insert into SummaryStream;
    """
    serial_app = app.replace("@app:shards(n='4', key='symbol')\n    ", "") \
                    .replace("ShardedBench", "ShardedBenchSerial")

    _phase("sharded_e2e:encode")
    rng = np.random.default_rng(RNG_SEED + 3)

    def make_body(n_rows: int, seed_frames: int):
        ks = rng.integers(0, n_keys, n_rows)
        cols = {
            "symbol": np.array([f"S{int(k)}" for k in ks], dtype=object),
            # multiples of 0.25: every per-key partial sum is exactly
            # representable, so the parity digest is bit-stable
            "price": rng.integers(1, 4000, n_rows) * 0.25,
            "volume": rng.integers(1, 1000, n_rows),
        }
        return cols

    parity_cols = make_body(8192, 4)
    bodies = []
    for _p in range(n_producers):
        per = []
        for _ in range(3):
            cols = make_body(eb, 1)
            per.append(cols)
        bodies.append(per)

    def encode_all(defn):
        plan = wire.schema_plan(defn)
        pbody = wire.encode_frames(plan, parity_cols, 8192, chunk=2048)
        tbodies = [[wire.encode_frames(plan, cols, eb) for cols in per]
                   for per in bodies]
        return pbody, tbodies

    def digest(rows) -> str:
        canon = "\n".join(repr(r) for r in sorted(rows))
        return hashlib.sha256(canon.encode()).hexdigest()

    def run_one(text, app_name, n_sh):
        if n_sh is not None:
            os.environ["SIDDHI_SHARDS"] = str(n_sh)
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(text, batch_size=eb,
                                               async_callbacks=True)
        finally:
            os.environ.pop("SIDDHI_SHARDS", None)
        svc = SiddhiService(mgr)
        rows_out = []
        collecting = [True]
        n_out = [0]

        def cb(events):
            n_out[0] += len(events)
            if collecting[0]:
                rows_out.extend(tuple(e.data) for e in events)

        rt.add_callback("SummaryStream", cb)
        rt.start()
        h = rt.get_input_handler("TradeStream")
        defn = getattr(h, "definition", None) or h.junction.definition
        pbody, tbodies = encode_all(defn)

        # phase A: deterministic parity feed (single producer). drain()
        # barriers the decoder, but @Async junctions hand rows to feeder
        # threads first — settle on the EXACT expected row count (the
        # filter's pass count is deterministic) so the digest never
        # samples mid-flight
        expected = int((parity_cols["price"] < 700.0).sum())
        svc.send_frames(app_name, "TradeStream", pbody)
        settle_by = time.monotonic() + 60.0
        while True:
            rt.drain()
            if len(rows_out) >= expected or time.monotonic() > settle_by:
                break
            time.sleep(0.02)
        assert len(rows_out) == expected, (len(rows_out), expected)
        dg = digest(rows_out)
        collecting[0] = False
        rows_out.clear()

        # phase B: multi-producer throughput
        def producer(p, n_rounds, r0):
            per = tbodies[p]
            for r in range(n_rounds):
                svc.send_frames(app_name, "TradeStream",
                                per[(r0 + r) % len(per)])

        def run_rounds(n_rounds, r0):
            ts = [threading.Thread(target=producer, args=(p, n_rounds, r0),
                                   name=f"shard-producer-{p}")
                  for p in range(n_producers)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            rt.drain()

        rounds = 2 if cpu else 4
        # warm the compile ladders off the clock with the SAME queued
        # multi-producer shape as the timed reps: back-to-back frames
        # coalesce into larger micro-batches, and every coalesced bucket
        # is a fresh executable — a single-frame warm pass would leave
        # those compiles inside the measurement window
        for _w in range(2):
            run_rounds(rounds, 0)
        best = 0.0
        r0 = 1
        for _rep in range(2):
            t0 = time.perf_counter()
            run_rounds(rounds, r0)
            best = max(best, n_producers * rounds * eb
                       / (time.perf_counter() - t0))
            r0 += rounds
        conserved = None
        if hasattr(rt, "conservation_report"):
            conserved = rt.conservation_report()["conserved"]
        rt.shutdown()
        assert n_out[0] > 0, f"{app_name}: no output — not a valid measure"
        return dg, best, conserved

    _phase("sharded_e2e:serial")
    dg_serial, _rate_serial, _ = run_one(serial_app, "ShardedBenchSerial",
                                         None)
    rates = {}
    digests = {"serial": dg_serial}
    conservation = {}
    for n_sh in (1, 4, 8):
        _phase(f"sharded_e2e:shards{n_sh}")
        dg, rate, conserved = run_one(app, "ShardedBench", n_sh)
        rates[n_sh] = rate
        digests[n_sh] = dg
        conservation[n_sh] = conserved
        _partial({f"shards_{n_sh}_events_per_sec": round(rate, 1),
                  f"shards_{n_sh}_conserved": conserved,
                  f"shards_{n_sh}_parity": dg == dg_serial})

    parity = all(d == dg_serial for d in digests.values())
    value = round(rates[4], 1)
    res = {
        "metric": "sharded_e2e_events_per_sec",
        "value": value,
        "unit": "events/sec",
        "vs_baseline": round(
            value / _baseline_for("sharded_e2e_events_per_sec"), 3),
        "shards_1": round(rates[1], 1),
        "shards_4": round(rates[4], 1),
        "shards_8": round(rates[8], 1),
        "scaling_x4": round(rates[4] / max(rates[1], 1e-9), 3),
        "scaling_x8": round(rates[8] / max(rates[1], 1e-9), 3),
        "parity": parity,
        "conserved": all(bool(c) for c in conservation.values()),
        "producers": n_producers,
    }
    _phase("sharded_e2e:failover")
    res["failover"] = _bench_failover_leg()
    _partial(res)
    assert parity, f"shard-vs-serial output digests diverged: {digests}"
    if not E2E_ONLY:
        res.update(_preflight(app))
    return res


def _fanout_app(n_queries: int) -> str:
    """N co-resident queries over ONE stream: filters with distinct
    thresholds, every 32nd a windowless group-by aggregate (sum + count per
    symbol) — all shape-polymorphic, so the optimizer fuses maximal runs
    into SharedStepGroups. Windowless aggregates rather than time windows:
    window machinery costs ~100x a filter per step and would drown the
    dispatch-bound regime this config measures in both modes."""
    lines = [
        "@app:name('FanoutBench')",
        "define stream TradeStream (symbol string, price double, "
        "volume long);",
    ]
    for i in range(n_queries):
        if i % 64 == 63:
            lines.append(
                f"@info(name='agg{i}') from TradeStream "
                f"select symbol, sum(price) as total, count() as n "
                f"group by symbol insert into AggOut{i};")
        else:
            thr = (i * 900.0) / max(n_queries, 1)
            lines.append(
                f"@info(name='filt{i}') from TradeStream[price > {thr:.1f}] "
                f"select symbol, price insert into FiltOut{i};")
    return "\n".join(lines)


def bench_fanout() -> dict:
    """HEADLINE config: multi-tenant fan-out — N ∈ {1, 16, 64, 256}
    filter/aggregate queries over one stream fed via SXF1 binary frames
    through the service surface, with the multi-query optimizer ON vs OFF.
    Reports events/s and the XLA compile count at each N: with the optimizer
    the compile count stays O(fused groups) while throughput holds; without
    it both scale linearly with N (the paper's multi-tenant cost problem,
    ROADMAP open item #1). Also records e2e_rows_events_per_sec — the
    row-at-a-time compatibility tier's measured number (VERDICT item 10)."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.io import wire
    from siddhi_tpu.service import SiddhiService

    cpu = _is_cpu()
    # dispatch-bound regime ON PURPOSE: small batches + many queries is
    # where per-query dispatch dominates and fusion pays. At large batches
    # the run is compute-bound and both modes converge on the same XLA work.
    bb = int(os.environ.get("SIDDHI_FANOUT_BATCH", 0)) or 128
    # group_capacity bounds the per-aggregate key table; the repo default
    # (1 << 20 slots) makes each group-by step carry million-entry state —
    # pure overhead at 100 distinct keys.
    gc = int(os.environ.get("SIDDHI_FANOUT_GROUP_CAPACITY", 0)) or 4096
    n_keys = 100
    rng = np.random.default_rng(RNG_SEED + 3)
    res: dict = {"metric": "fanout256_events_per_sec", "unit": "events/sec",
                 "batch": bb, "group_capacity": gc}
    deadline = time.monotonic() + max(CONFIG_SECONDS - 30.0, 60.0)

    def run_mode(n_queries: int, optimize: bool, rounds: int):
        app = _fanout_app(n_queries)
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(app, batch_size=bb,
                                           group_capacity=gc,
                                           optimize=optimize)
        svc = SiddhiService(mgr)
        n_out = [0]
        rt.add_callback("FiltOut0", lambda blk: n_out.__setitem__(
            0, n_out[0] + blk.count), columnar=True)
        rt.start()
        _warmup_or_die(rt, (bb,))
        plan = wire.schema_plan(rt.junctions["TradeStream"].definition)
        bodies = []
        for _ in range(3):
            ks = rng.integers(1, n_keys + 1, bb)
            cols = {
                "symbol": np.array([f"S{int(k)}" for k in ks], dtype=object),
                "price": rng.uniform(1.0, 1000.0, bb),
                "volume": rng.integers(1, 1000, bb),
            }
            bodies.append(wire.encode_frames(plan, cols, bb))

        def run_rounds(k: int, r0: int) -> None:
            for r in range(k):
                svc.send_frames("FanoutBench", "TradeStream",
                                bodies[(r0 + r) % len(bodies)])
            rt.drain()

        run_rounds(2, 0)  # residual compiles (partial shapes) out of measure
        best, r0 = 0.0, 2
        for _rep in range(3):
            t0 = time.perf_counter()
            run_rounds(rounds, r0)
            elapsed = time.perf_counter() - t0
            r0 += rounds
            best = max(best, rounds * bb / elapsed)
        rep = rt.statistics_report()
        compiles = sum(rep["compiles"].values())
        opt_section = rep.get("optimizer", {})
        rt.shutdown()
        assert n_out[0] > 0, "fanout produced no output — not a valid measure"
        return best, compiles, opt_section

    # small-batch regime: enough rounds that each timed rep spans >100 ms
    # even in the fast (fused) mode, or rep-to-rep jitter dominates
    rounds = 24 if cpu else 32
    fanout_ns = (1, 16, 64, 256)
    for n in fanout_ns:
        if time.monotonic() > deadline and n > 1:
            _partial({f"fanout{n}_skipped": "config budget exhausted"})
            continue
        _phase(f"fanout:{n}q:optimizer_on")
        ev_on, comp_on, opt = run_mode(n, True, rounds)
        _partial({f"fanout{n}_on_events_per_sec": round(ev_on, 1),
                  f"fanout{n}_on_compiles": comp_on,
                  f"fanout{n}_groups": opt.get("groups", 0),
                  f"fanout{n}_queries_fused": opt.get("queries_fused", 0),
                  f"fanout{n}_compiles_avoided":
                      opt.get("compiles_avoided", 0)})
        res.update(PARTIAL)
        if time.monotonic() > deadline and n > 1:
            _partial({f"fanout{n}_off_skipped": "config budget exhausted"})
            continue
        _phase(f"fanout:{n}q:optimizer_off")
        ev_off, comp_off, _ = run_mode(n, False, rounds)
        _partial({f"fanout{n}_off_events_per_sec": round(ev_off, 1),
                  f"fanout{n}_off_compiles": comp_off,
                  f"fanout{n}_speedup": round(ev_on / max(ev_off, 1e-9), 2)})
        res.update(PARTIAL)

    # headline value: optimizer-on events/s at the largest N that completed
    for n in reversed(fanout_ns):
        v = res.get(f"fanout{n}_on_events_per_sec")
        if v is not None:
            res["value"] = v
            res["headline_n_queries"] = n
            break
    res["vs_baseline"] = round(
        res.get("value", 0.0) / _baseline_for("fanout256_events_per_sec"), 3)

    # rows-path compatibility tier: the same public path fed with per-row
    # Python tuples + per-Event callbacks (VERDICT item 10's missing number)
    _phase("fanout:rows_path")
    eb = _resolve_e2e_batch()
    app1 = _fanout_app(1)
    rt3 = SiddhiManager().create_siddhi_app_runtime(
        app1, batch_size=eb, async_callbacks=True)
    rows = _trade_rows(4, n_keys, price_hi=1000.0, n=eb)
    h3 = rt3.get_input_handler("TradeStream")

    def feed_rows(r):
        h3.send_batch(rows[r % len(rows)])
        rt3.flush()

    res["e2e_rows_events_per_sec"] = round(
        _measure_e2e(rt3, "FiltOut0", feed_rows, eb,
                     columnar=False, rounds=4), 1)
    _partial({"e2e_rows_events_per_sec": res["e2e_rows_events_per_sec"]})
    if not E2E_ONLY:
        res.update(_preflight(_fanout_app(16)))
    return res


def _churn_query(i: int) -> str:
    thr = (i * 900.0) / 1024.0
    return (f"@info(name='cq{i}') from TradeStream[price > {thr:.1f}] "
            f"select symbol, price insert into ChurnOut{i};")


def _churn_app(n_queries: int) -> str:
    lines = [
        "@app:name('ChurnBench')",
        "define stream TradeStream (symbol string, price double, "
        "volume long);",
    ]
    for i in range(n_queries):
        lines.append(_churn_query(i))
    return "\n".join(lines)


def bench_churn() -> dict:
    """Churn drill: Poisson attach/detach against a live fused fleet under
    sustained SXF1 traffic (the multi-tenant churn proof). Queries splice
    into/out of live SharedStepGroups with ONE retrace — no drain, no
    stop-the-world redeploy — so the bar is threefold: attach deploy
    latency p50/p99 (parse → spliced → warmed), the throughput of the
    block of rounds IMMEDIATELY after each splice vs a settled block at
    the same membership (churn_splice_throughput_ratio, advisory floor
    0.9 — no cliff at splice points; pairing at equal membership keeps
    deliberate fleet growth from masquerading as one), and bit-identical
    output from a sampled spliced-in query vs a from-scratch
    single-query build fed identical frames.
    SIDDHI_STATE_BUDGET is set for the drill so EVERY attach is priced by
    the per-splice SL501 admission gate (one deliberately oversized attach
    proves refusal), and the final fleet must sit under the budget.
    SIDDHI_CHURN_QUERIES scales the drill (default 1000 on accelerators,
    64 on CPU where each retrace is an XLA:CPU compile)."""
    from siddhi_tpu import SiddhiManager, compiler
    from siddhi_tpu.analysis.cost import compute_cost
    from siddhi_tpu.errors import SiddhiAppCreationError
    from siddhi_tpu.io import wire
    from siddhi_tpu.service import SiddhiService

    cpu = _is_cpu()
    total_q = int(os.environ.get("SIDDHI_CHURN_QUERIES", 0)) or \
        (64 if cpu else 1000)
    base_n = max(2, min(64, total_q // 4))
    bb = int(os.environ.get("SIDDHI_FANOUT_BATCH", 0)) or 128
    n_keys = 100
    rng = np.random.default_rng(RNG_SEED + 5)
    res: dict = {"metric": "churn_sustained_events_per_sec",
                 "unit": "events/sec", "batch": bb,
                 "queries_target": total_q, "queries_base": base_n}
    deadline = time.monotonic() + max(CONFIG_SECONDS - 30.0, 60.0)

    # price the FULL drill fleet once and set the budget with headroom:
    # admission control runs on every attach without starving the churn
    budget = int(compute_cost(compiler.parse(_churn_app(total_q)),
                              batch_size=bb).state_bytes * 1.5) + 1
    os.environ["SIDDHI_STATE_BUDGET"] = str(budget)

    _phase("churn:build")
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(_churn_app(base_n), batch_size=bb,
                                       optimize=True)
    svc = SiddhiService(mgr)
    rt.start()
    _warmup_or_die(rt, (bb,))
    plan = wire.schema_plan(rt.junctions["TradeStream"].definition)
    bodies = []
    for _ in range(3):
        ks = rng.integers(1, n_keys + 1, bb)
        cols = {
            "symbol": np.array([f"S{int(k)}" for k in ks], dtype=object),
            "price": rng.uniform(1.0, 1000.0, bb),
            "volume": rng.integers(1, 1000, bb),
        }
        bodies.append(wire.encode_frames(plan, cols, bb))
    r = [0]

    def send_round() -> None:
        svc.send_frames("ChurnBench", "TradeStream",
                        bodies[r[0] % len(bodies)])
        r[0] += 1

    # no-churn baseline: median over blocks of the SAME shape the drill
    # times at each splice point (BLOCK rounds + one drain), so the ratio
    # compares like with like; the block is wide enough that the one-time
    # post-attach first-touch (~1 ms of lazy output-path init) reads as
    # the noise it is, not as a sustained cliff
    BLOCK = 24

    def block_rate() -> float:
        t0 = time.perf_counter()
        for _ in range(BLOCK):
            send_round()
        rt.drain()
        return BLOCK * bb / (time.perf_counter() - t0)

    _phase("churn:baseline")
    for _ in range(4):
        send_round()
    rt.drain()
    base_rate = float(np.median([block_rate() for _ in range(3)]))
    _partial({"churn_no_churn_events_per_sec": round(base_rate, 1)})

    # the drill: Poisson-paced attach/detach under continuous traffic
    _phase("churn:drill")
    deploy_ms: list = []
    post_splice_rates: list = []
    attaches = detaches = refused = 0
    active = list(range(base_n))
    next_i = base_n
    ev_total = 0
    churn_t0 = time.perf_counter()
    while next_i < total_q and time.monotonic() < deadline:
        for _ in range(1 + int(rng.poisson(1.0))):
            send_round()
            ev_total += bb
        rt.drain()
        if len(active) > base_n and rng.random() < 0.35:
            victim = active.pop(int(rng.integers(len(active))))
            mgr.detach_query("ChurnBench", f"cq{victim}")
            detaches += 1
        else:
            try:
                out = mgr.attach_query("ChurnBench", _churn_query(next_i))
            except SiddhiAppCreationError:
                refused += 1
                next_i += 1
                continue
            deploy_ms.append(out["deploy_ms"])
            attaches += 1
            active.append(next_i)
            next_i += 1
            # no-cliff check AT the splice point: the block of rounds
            # immediately after the splice vs a settled block right after
            # it — SAME membership, so fleet growth (more queries = more
            # work per batch, by design) doesn't masquerade as a cliff
            at_splice = block_rate()
            settled = block_rate()
            post_splice_rates.append(at_splice / max(settled, 1e-9))
            ev_total += 2 * BLOCK * bb
        if (attaches + detaches) % 32 == 0 and deploy_ms:
            _partial({"churn_attaches": attaches,
                      "churn_detaches": detaches,
                      "churn_deploy_p99_ms": round(
                          float(np.percentile(deploy_ms, 99)), 2)})
    churn_elapsed = time.perf_counter() - churn_t0

    # one deliberately oversized attach: the per-splice SL501 gate must
    # refuse it (splices never queue) without disturbing the fleet
    _phase("churn:admission")
    try:
        mgr.attach_query(
            "ChurnBench",
            "@info(name='cqbig') from TradeStream#window.length(1048576) "
            "select symbol, sum(price) as t insert into BigOut;")
        sl501_ok = 0.0
    except SiddhiAppCreationError:
        refused += 1
        sl501_ok = 1.0
    predicted = int(rt.cost_report.get("predicted_state_bytes", 0))
    assert predicted <= budget, \
        f"fleet {predicted} over SIDDHI_STATE_BUDGET {budget}"

    # oracle digest: the most recently spliced-in survivor must match a
    # from-scratch single-query build bit-for-bit on identical frames
    _phase("churn:oracle")
    sample = active[-1]
    got_live: list = []
    rt.add_callback(f"ChurnOut{sample}", lambda evs: got_live.extend(
        tuple(e.data) for e in evs))
    for _ in range(4):
        send_round()
    rt.drain()
    m2 = SiddhiManager()
    rt2 = m2.create_siddhi_app_runtime(
        "@app:name('ChurnBench')\n"
        "define stream TradeStream (symbol string, price double, "
        "volume long);\n" + _churn_query(sample),
        batch_size=bb, optimize=False)
    got_scratch: list = []
    rt2.add_callback(f"ChurnOut{sample}", lambda evs: got_scratch.extend(
        tuple(e.data) for e in evs))
    rt2.start()
    svc2 = SiddhiService(m2)
    for i in range(r[0] - 4, r[0]):
        svc2.send_frames("ChurnBench", "TradeStream",
                         bodies[i % len(bodies)])
    rt2.drain()
    assert got_live and got_live == got_scratch, \
        "spliced-in query diverged from its from-scratch build"
    rt2.shutdown()

    stats = rt.statistics_report()
    opt = rt.optimizer_report or {}
    rt.shutdown()
    os.environ.pop("SIDDHI_STATE_BUDGET", None)
    ratio = (float(np.median(post_splice_rates))
             if post_splice_rates else 0.0)
    res.update({
        "value": round(ev_total / churn_elapsed, 1),
        "churn_no_churn_events_per_sec": round(base_rate, 1),
        "churn_splice_throughput_ratio": round(ratio, 3),
        "churn_deploy_p50_ms": round(
            float(np.percentile(deploy_ms, 50)), 2) if deploy_ms else None,
        "churn_deploy_p99_ms": round(
            float(np.percentile(deploy_ms, 99)), 2) if deploy_ms else None,
        "churn_attaches": attaches,
        "churn_detaches": detaches,
        "churn_sl501_refused": refused,
        "churn_sl501_gate_ok": sl501_ok,
        "churn_oracle_ok": 1.0,
        "churn_queries_final": len(active),
        "churn_groups": opt.get("groups", 0),
        "churn_splices": (stats.get("splices") or {}).get("counts", {}),
        "churn_state_budget_bytes": budget,
        "churn_predicted_state_bytes": predicted,
    })
    _partial({k: res[k] for k in res if k.startswith("churn_")})
    res["vs_baseline"] = round(
        res["value"] / _baseline_for("churn_sustained_events_per_sec"), 3)
    if not E2E_ONLY:
        res.update(_preflight(_churn_app(16)))
    return res


def bench_hang() -> dict:
    """HIDDEN config (`python bench.py _hang`): deliberately wedges before
    importing anything heavy AND swallows the in-process alarm — the
    watchdog unit test proves the PARENT deadline bounds even a config the
    child-side alarm cannot stop, while the partials still yield a JSON
    line."""
    _partial({"metric": "hang_test", "stage_one": 1.0})
    _phase("_hang:sleeping")
    while True:
        try:
            time.sleep(3600)
        except BenchTimeout:
            pass  # simulate a hang no Python-level bound can interrupt


CONFIGS = {
    "filter": bench_filter,
    "distinct": bench_distinct,
    "pattern": bench_pattern,
    "join": bench_join,
    "overload": bench_overload,  # bounded ingress under 10x overload
    "disorder": bench_disorder,  # out-of-order ingress through the
    # @app:eventTime gate: gated rate + exact late-diversion counts
    "upgrade": bench_upgrade,  # blue-green hot-swap under live traffic
    "groupby": bench_groupby,
    "e2e_ingress": bench_e2e_ingress,  # wire→pipeline→device rate
    "sharded_e2e": bench_sharded_e2e,  # partition-key shard plane: parity,
    # conservation, and same-host scaling at shards {1, 4, 8}
    "churn": bench_churn,  # Poisson attach/detach splice drill: deploy
    # latency p50/p99, no-cliff ratio at splice points, SL501 per splice
    "fanout": bench_fanout,  # HEADLINE: keep last — drivers that parse only
    # the final line track the multi-tenant shared-execution rate
}
#: not part of the default run; reachable by explicit name only
HIDDEN_CONFIGS = {"_hang": bench_hang}


def _run_config_subprocess(argv, env=None, timeout: float = 900.0):
    """Run one config in a fresh interpreter under a HARD parent deadline.
    The child's stdout is streamed live: `#partial {json}` checkpoint lines
    accumulate so a killed child still yields numbers for every sub-metric
    that finished (merged under "partial": true). stderr (heartbeats)
    passes straight through to our stderr."""
    import subprocess
    t0 = time.monotonic()
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, stderr=None,
                            text=True, env=env)
    partial: dict = {}
    final: list = []

    def _reader():
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("#partial "):
                try:
                    partial.update(json.loads(line[len("#partial "):]))
                except json.JSONDecodeError:
                    pass
            elif line.startswith("{"):
                final.append(line)

    rd = threading.Thread(target=_reader, daemon=True)
    rd.start()
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:  # pragma: no cover — kill -9'd
            pass
        rd.join(timeout=5)
        elapsed = time.monotonic() - t0
        return {**partial, "partial": True,
                "error": f"timeout after {elapsed:.0f}s"}
    rd.join(timeout=10)
    if not final:
        if partial:  # child died mid-run (alarm/OOM) but checkpointed
            return {**partial, "partial": True,
                    "error": f"config exited rc={proc.returncode} "
                             "before the final line"}
        return {"error": f"no output (rc={proc.returncode})"}
    try:
        return json.loads(final[-1])
    except json.JSONDecodeError:
        return {"error": final[-1][-400:]}


def _run_child(name: str) -> None:
    """Child mode: one config, best-effort SIGALRM + heartbeat, partial
    JSON on expiry. The parent's kill is the hard bound; the alarm lets a
    Python-visible stall report its own partials first."""
    fn = {**CONFIGS, **HIDDEN_CONFIGS}[name]
    _arm_child_watchdog(max(CONFIG_SECONDS - 5.0, 1.0))
    try:
        if name != "_hang":  # _hang must stay import-free
            _resolve_e2e_batch()
            import jax
            from siddhi_tpu.util.platform import configure_compile_cache
            configure_compile_cache()
            # every line names what it ran on; the parent also skips its
            # colocated-CPU pass when this child already ran on CPU (same
            # backend twice = wasted budget)
            _partial({"backend": jax.default_backend(),
                      "device_kind": jax.devices()[0].device_kind})
        res = fn()
        for k in ("backend", "device_kind"):
            res.setdefault(k, PARTIAL.get(k))
    except BenchTimeout as e:
        res = {**PARTIAL, "partial": True, "error": str(e)}
        res.setdefault("metric", name)
    print(json.dumps(res), flush=True)


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    known = {**CONFIGS, **HIDDEN_CONFIGS}
    unknown = [n for n in args if n not in known]
    if unknown:
        sys.exit(f"unknown config(s) {unknown}; choose from {list(CONFIGS)}")
    names = args or list(CONFIGS)
    # child mode is EXPLICIT (--child / --e2e-only): a bare single-config
    # invocation still gets the parent-side watchdog
    if E2E_ONLY or "--child" in sys.argv:
        if E2E_ONLY and os.environ.get("SIDDHI_BENCH_CPU"):
            # co-located variant: same engine, CPU backend in-process — the
            # "device" is host memory, so this isolates the host path
            from siddhi_tpu.util.platform import force_cpu_platform
            force_cpu_platform(1)
        _run_child(names[0])
        return
    # one subprocess per config: earlier configs' runtimes pin device buffers
    # (1M-key tables, 100k rings) and degrade later configs measurably when
    # sharing a process. Per-config deadline = the config's FAIR SHARE of
    # the remaining outer budget (capped at CONFIG_SECONDS): one slow early
    # config can no longer eat the tail configs' slices — the run always
    # reaches the headline (last) config and emits its final JSON line
    # inside the driver's wall limit. Unused share rolls forward.
    for i, name in enumerate(names):
        remaining = MAX_SECONDS - (time.monotonic() - T0)
        left = len(names) - i
        if remaining < 20:
            print(json.dumps({
                "metric": name, "error": "skipped: --max-seconds budget "
                f"exhausted ({MAX_SECONDS:.0f}s)"}), flush=True)
            continue
        budget = min(CONFIG_SECONDS, max(remaining / left, 20.0), remaining)
        print(f"[bench] t={time.monotonic() - T0:.0f}s config={name} "
              f"({i + 1}/{len(names)}) budget={budget:.0f}s "
              f"(fair share of {remaining:.0f}s over {left})",
              file=sys.stderr, flush=True)
        res = _run_config_subprocess(
            [sys.executable, __file__, name, "--child",
             f"--config-seconds={budget:.0f}"],
            timeout=budget)
        res.setdefault("metric", name)
        if "error" in res and not res.get("partial"):
            print(json.dumps(res), flush=True)
            continue
        # co-located CPU e2e (VERDICT r3 item 1: separate topology from
        # engine): same public path, CPU backend, fresh subprocess. Skipped
        # when the primary child already ran on CPU (it IS the co-located
        # number), and bounded so the configs still queued keep a floor of
        # ~45 s each of the remaining budget.
        remaining = MAX_SECONDS - (time.monotonic() - T0)
        reserve = 45.0 * (len(names) - i - 1)
        if (remaining - reserve > 30 and "error" not in res
                and res.get("backend") != "cpu"):
            cpu_budget = min(90.0, CONFIG_SECONDS, remaining - reserve)
            cpu_env = dict(os.environ,
                           JAX_PLATFORMS="cpu", SIDDHI_BENCH_CPU="1")
            cpu = _run_config_subprocess(
                [sys.executable, __file__, name, "--e2e-only",
                 f"--config-seconds={cpu_budget:.0f}"],
                env=cpu_env, timeout=cpu_budget)
            if "e2e_events_per_sec" in cpu:
                res["e2e_colocated_events_per_sec"] = cpu["e2e_events_per_sec"]
            if "p99_autoflush_latency_ms" in cpu:
                res["p99_autoflush_latency_ms_colocated"] = \
                    cpu["p99_autoflush_latency_ms"]
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
