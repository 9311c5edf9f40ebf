#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip.

Drives the engine's main path end to end on one TPU, through the entry
points a user calls, at the flagship deployment's real width, and checks
every emitted row against a plain per-event reference kept in this file:

    SXF1 frames --HTTP POST--> SiddhiService --> @Async(workers=4) ingress
    pipeline --> junction --> jitted step on the device --> async read-back
    --> columnar callback

The deployment is a two-query ingress app (filter `price < 700.0` →
`lengthBatch(10000)` sum/avg/count group by symbol, fed by one
`@Async(workers=4)` stream) at the flagship's key count: 1,000,000
distinct symbols, 131,072-lane batches, group capacity 2**20. Prices are
multiples of 0.25, so float32 partial sums are exact and the comparison can
be too.

  phase A  one producer, 16 frames (2.1M events): every emitted row equals
           the reference's, in order.
  phase B  4 producers at once, 8 frames each, same runtime: arrival order
           is not deterministic, so the order is read back from the emitted
           timestamps (each event's is unique) and checked for conservation
           — nothing lost, nothing duplicated, nothing dropped, each
           producer's rows in its own order, every window full — and then
           the rows are compared with the reference run in that order.
  phase C  the same app under `@app:superstep(k='8')` on a fresh runtime,
           24 frames: equal to the reference, with supersteps dispatched
           and none declined.

It also asserts that the mechanisms engaged (ingress pipeline, native
module, state on the device, no compile inside a fed window), bounds every
wait with a deadline that dumps all thread stacks, and fails on anything
the engine logged at ERROR or announced as a fallback.

`python chip_smoke.py` always requires the chip: it refuses any other
platform, never sets JAX_PLATFORMS, and exits non-zero without a result
line when there is no TPU. `run_smoke` is the importable body; tests call
it at toy size on the CPU. Standard output is two lines of JSON: the full
report (beginning {"ok": ..., "device": {...}}, ending "claim": null), then,
last, the verdict alone with exactly these keys —
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.
The wall seconds in the report are a sighting of one run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import http.client
import json
import logging
import os
import sys
import threading
import time

import numpy as np

PRICE_CUT = 700.0  # the filter's literal, `price < 700.0`

APP = """
@app:name('{name}')
{superstep}@Async(buffer.size='{batch}', workers='{workers}')
define stream TradeStream (symbol string, price double, volume long);
@info(name = 'filt')
from TradeStream[price < 700.0]
select symbol, price, volume
insert into MidStream;
@info(name = 'agg')
from MidStream#window.lengthBatch({window})
select symbol, sum(price) as total, avg(price) as avgPrice, count() as n
group by symbol
insert into SummaryStream;
"""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The deployment's scale. Defaults are the flagship's; tests shrink
    them. `batch` is the @Async buffer, the runtime batch size and the rows
    per frame at once, so only the full-width step is ever dispatched."""

    batch: int = 131072
    keys: int = 1_000_000
    group_capacity: int = 1 << 20
    window: int = 10_000
    workers: int = 4
    superstep_k: int = 8
    warm_frames: int = 2  # after the first; phase C warms one full superstep
    frames_a: int = 16
    producers_b: int = 4
    frames_b: int = 8  # per producer
    frames_c: int = 24
    phase_deadline_s: float = 240.0
    warmup_deadline_s: float = 420.0


class SmokeError(Exception):
    """A phase could not run to its end (as opposed to a failed check)."""


# --------------------------------------------------------------- reference


def reference_rows(sym, price, ts, window: int):
    """The query, one event at a time, in plain Python: keep `price < 700`;
    cut the kept events into consecutive windows of `window`; inside a
    window every event emits its symbol's running sum, average and count;
    a window's rows come out when it is full. Returns (ts, sym, total, avg,
    n) arrays over the emitted rows. Independent of siddhi_tpu."""
    keep = price < PRICE_CUT
    sym_l = sym[keep].tolist()
    price_l = price[keep].tolist()
    n_full = (len(sym_l) // window) * window
    total = np.empty(n_full, np.float32)
    count = np.empty(n_full, np.int64)
    for w0 in range(0, n_full, window):
        sums: dict = {}
        counts: dict = {}
        for i in range(w0, w0 + window):
            k = sym_l[i]
            s = sums.get(k, 0.0) + price_l[i]
            c = counts.get(k, 0) + 1
            sums[k] = s
            counts[k] = c
            total[i] = s
            count[i] = c
    avg = total / count.astype(np.float32)
    return ts[keep][:n_full], sym[keep][:n_full], total, avg, count


# ------------------------------------------------------- process-wide traps


class _CompileLog:
    """Counts what jax compiled and what its persistent cache answered, from
    jax.monitoring events. Registered once per process: jax offers no public
    way to take a listener back."""

    _instance = None

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.compiles: list = []  # (fun_name, seconds) per backend compile
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_requests = 0

    @classmethod
    def get(cls) -> "_CompileLog":
        if cls._instance is None:
            from jax import monitoring
            log = cls._instance = cls()
            monitoring.register_event_duration_secs_listener(log._on_duration)
            monitoring.register_event_listener(log._on_event)
        return cls._instance

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self.lock:
                self.compiles.append((kw.get("fun_name", "?"), seconds))

    def _on_event(self, event: str, **kw) -> None:
        with self.lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1
            elif event == "/jax/compilation_cache/compile_requests_use_cache":
                self.cache_requests += 1

    def mark(self) -> int:
        with self.lock:
            return len(self.compiles)

    def since(self, mark: int) -> list:
        with self.lock:
            return list(self.compiles[mark:])


class _EngineLogTrap(logging.Handler):
    """Fails the smoke on anything the engine swallowed: every record at
    ERROR or above on the `siddhi_tpu` logger, and every WARNING that
    announces a fallback or a wait that gave up."""

    TRIP_WORDS = ("declined", "falling back", "timed out", "did not stop",
                  "using the python encoder")

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.tripped: list = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if record.levelno >= logging.ERROR or any(
                w in msg.lower() for w in self.TRIP_WORDS):
            if record.exc_info and record.exc_info[1] is not None:
                msg += f" [{record.exc_info[1]!r}]"
            self.tripped.append(f"{record.levelname}: {msg}")


class _Deadline:
    """`with _Deadline(name, seconds):` — when the block outlives its
    deadline, say which phase it was, dump every thread's stack and exit
    non-zero. A Python thread does the naming; faulthandler's own timer (a
    C thread that needs no GIL) backs it up a few seconds later."""

    def __init__(self, phase: str, seconds: float) -> None:
        self.phase, self.seconds = phase, seconds
        self._done = threading.Event()

    def __enter__(self) -> "_Deadline":
        print(f"[chip_smoke] phase {self.phase} (deadline "
              f"{self.seconds:.0f}s)", file=sys.stderr, flush=True)
        faulthandler.dump_traceback_later(self.seconds + 10, exit=True)
        threading.Thread(target=self._watch, daemon=True,
                         name=f"smoke-deadline-{self.phase}").start()
        return self

    def _watch(self) -> None:
        if self._done.wait(self.seconds):
            return
        print(f"[chip_smoke] FAILED: phase {self.phase} exceeded its "
              f"{self.seconds:.0f}s deadline; thread stacks follow",
              file=sys.stderr, flush=True)
        faulthandler.dump_traceback(all_threads=True)
        os._exit(3)

    def __exit__(self, *exc) -> None:
        self._done.set()
        faulthandler.cancel_dump_traceback_later()


# ------------------------------------------------------------- the traffic


class _Traffic:
    """Seeded events for one runtime, kept as columns for the reference and
    as encoded SXF1 frame bodies for the wire. Every event's timestamp is
    its index in generation order, hence unique."""

    def __init__(self, rng, symbols, plan, sizes: Sizes) -> None:
        self.rng, self.symbols, self.plan, self.sizes = \
            rng, symbols, plan, sizes
        self.sym: list = []
        self.price: list = []
        self.bodies: list = []  # one encoded frame each

    def make(self, n_frames: int) -> list:
        """Generate and encode `n_frames` more frames; returns their indexes."""
        from siddhi_tpu.io import wire
        b = self.sizes.batch
        first = len(self.bodies)
        for f in range(first, first + n_frames):
            sym = self.rng.integers(0, self.sizes.keys, b)
            price = self.rng.integers(1, 4000, b) * 0.25
            cols = {"symbol": self.symbols[sym], "price": price,
                    "volume": self.rng.integers(1, 1000, b)}
            ts = np.arange(f * b, (f + 1) * b, dtype=np.int64)
            self.bodies.append(wire.encode_frames(self.plan, cols, b, ts=ts))
            self.sym.append(sym)
            self.price.append(price)
        return list(range(first, first + n_frames))

    def columns(self):
        sym = np.concatenate(self.sym)
        return sym, np.concatenate(self.price), \
            np.arange(sym.size, dtype=np.int64)


class _Deployment:
    """One runtime of `APP` with async callbacks, served over a real
    socket, with a columnar callback collecting the output."""

    def __init__(self, name: str, sizes: Sizes, superstep: bool) -> None:
        from siddhi_tpu import SiddhiManager
        from siddhi_tpu.service import SiddhiService
        self.name, self.sizes = name, sizes
        text = APP.format(
            name=name, batch=sizes.batch, workers=sizes.workers,
            window=sizes.window,
            superstep=(f"@app:superstep(k='{sizes.superstep_k}')\n"
                       if superstep else ""))
        self.mgr = SiddhiManager()
        self.rt = self.mgr.create_siddhi_app_runtime(
            text, batch_size=sizes.batch,
            group_capacity=sizes.group_capacity, async_callbacks=True)
        self.blocks: list = []
        self.rt.add_callback("SummaryStream", self.blocks.append,
                             columnar=True)
        self.rt.start()
        self.server = SiddhiService(self.mgr).make_server(port=0)
        self.port = self.server.server_address[1]
        self._serve = threading.Thread(target=self.server.serve_forever,
                                       daemon=True, name=f"smoke-http-{name}")
        self._serve.start()
        self.sent_rows = 0
        self.accepted_rows = 0
        self._count_lock = threading.Lock()

    # -- the client side: what a producer does over the socket

    def _request(self, method: str, path: str, body=None, ctype=None):
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.port, timeout=self.sizes.phase_deadline_s)
        try:
            headers = {"Content-Type": ctype} if ctype else {}
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def post_frames(self, bodies) -> None:
        for body in bodies:
            status, reply = self._request(
                "POST", f"/siddhi-apps/{self.name}/streams/TradeStream",
                body, "application/x-siddhi-frames")
            if status != 200:
                raise SmokeError(f"POST frames answered {status}: "
                                 f"{reply[:300]!r}")
            with self._count_lock:
                self.sent_rows += self.sizes.batch
                self.accepted_rows += json.loads(reply)["accepted"]

    def get(self, path: str) -> bytes:
        status, reply = self._request("GET", path)
        if status != 200:
            raise SmokeError(f"GET {path} answered {status}: {reply[:300]!r}")
        return reply

    # -- what came out

    def emitted(self):
        """(ts, symbol strings, total, avg, n, any_expired) over every row
        delivered to the callback so far, in delivery order."""
        blocks = list(self.blocks)

        def cat(parts, dtype):
            return np.concatenate([np.zeros(0, dtype), *parts])

        return (cat((b.timestamps for b in blocks), np.int64),
                np.array([s for b in blocks for s in b.strings("symbol")],
                         dtype=object),
                cat((b.column("total") for b in blocks), np.float32),
                cat((b.column("avgPrice") for b in blocks), np.float32),
                cat((b.column("n") for b in blocks), np.int64),
                any(bool(b.is_expired.any()) for b in blocks))

    def pipeline_stats(self) -> dict:
        return self.rt.statistics_report()["ingress_pipeline"].get(
            "TradeStream") or {}

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self._serve.join(timeout=10)
        self.rt.shutdown()


# --------------------------------------------------------------- the checks


def _compare(emitted, symbols, order, sym, price, ts, window: int,
             label: str, fails: list) -> dict:
    """Emitted rows (`_Deployment.emitted()`) against the reference run over
    the events in `order` (indexes into sym/price/ts). Exact, except the average: it is
    one float32 division, which the TPU does not round correctly (the v5e
    lands 1 ulp off numpy's on some rows) — its worst distance from the
    reference is reported in ulps and held to 1."""
    e_ts, e_sym, e_total, e_avg, e_n, expired = emitted
    r_ts, r_sym, r_total, r_avg, r_n = reference_rows(
        sym[order], price[order], ts[order], window)
    out = {"rows_out": int(e_ts.size), "rows_expected": int(r_ts.size)}
    if expired:
        fails.append(f"{label}: expired rows on an insert-into stream")
    if e_ts.size != r_ts.size:
        fails.append(f"{label}: {e_ts.size} rows out, reference has "
                     f"{r_ts.size}")
        out["exact_match"] = False
        return out
    same = {
        "timestamp": np.array_equal(e_ts, r_ts),
        "symbol": bool(np.all(e_sym == symbols[r_sym])),
        "total": np.array_equal(e_total, r_total),
        "n": np.array_equal(e_n, r_n),
    }
    ulp = np.abs(e_avg.view(np.int32).astype(np.int64)
                 - r_avg.view(np.int32).astype(np.int64))
    out["avg_max_ulp"] = int(ulp.max()) if ulp.size else 0
    same["avgPrice"] = out["avg_max_ulp"] <= 1
    for col, ok in same.items():
        if not ok:
            fails.append(f"{label}: column {col!r} differs from the "
                         "reference")
    out["exact_match"] = all(same.values())
    return out


def _conservation(dep: _Deployment, e_ts, producer_ranges, price,
                  window: int, fails: list) -> tuple[dict, np.ndarray]:
    """Phase B's invariants, over everything this runtime was ever sent:
    each emitted timestamp is a sent event that passed the filter, none
    twice; exactly window × ⌊passed/window⌋ rows came out; each concurrent
    producer's rows keep their own order; nothing was dropped on the way
    in. `e_ts` are the emitted timestamps; returns the arrival order they
    reveal."""
    passed = int((price < PRICE_CUT).sum())
    stats = dep.rt.statistics_report()
    pipe = stats["ingress_pipeline"].get("TradeStream") or {}
    in_range = bool(e_ts.size == 0 or
                    (e_ts.min() >= 0 and e_ts.max() < price.size))
    checks = {
        "accepted_equals_sent": dep.accepted_rows == dep.sent_rows,
        "pipeline_rows_in_equals_sent":
            pipe.get("rows_in") == dep.sent_rows,
        "ingress_dropped_zero": not stats["ingress_dropped"],
        "rows_out_is_full_windows":
            int(e_ts.size) == window * (passed // window),
        "no_duplicates": np.unique(e_ts).size == e_ts.size,
        "only_sent_events_that_passed":
            in_range and bool(np.all(price[e_ts] < PRICE_CUT)),
        "producer_order_kept": all(
            bool(np.all(np.diff(e_ts[(e_ts >= lo) & (e_ts < hi)]) > 0))
            for lo, hi in producer_ranges),
    }
    for name, ok in checks.items():
        if not ok:
            fails.append(f"B: conservation check {name} failed")
    out = {"conserved": all(checks.values()), "checks": checks,
           "events_passed": passed, "rows_out": int(e_ts.size),
           "ingress_dropped": stats["ingress_dropped"]}
    return out, (e_ts if in_range else e_ts[:0])


def _state_off_device(rt, platform: str) -> list:
    """Names of query-state leaves that do not live on a `platform` device."""
    import jax
    bad = []
    for name, qr in rt.query_runtimes.items():
        for i, leaf in enumerate(jax.tree_util.tree_leaves(qr.state)):
            devs = getattr(leaf, "devices", None)
            if devs is None or any(d.platform != platform for d in devs()):
                bad.append(f"{name}[{i}]:{type(leaf).__name__}")
    return bad


def _engine_compiles(rt) -> int:
    return sum(rt.statistics.compiles.values())


def _fed_window(dep: _Deployment, label: str, feed, deadline_s: float,
                report: dict, fails: list) -> None:
    """Run `feed()` then drain, inside a deadline, and require that nothing
    compiled meanwhile: neither a step retrace (the engine's `compiles`
    counter) nor any backend compile at all."""
    clog = _CompileLog.get()
    c0, m0 = _engine_compiles(dep.rt), clog.mark()
    with _Deadline(label, deadline_s):
        t0 = time.perf_counter()
        feed()
        dep.rt.drain(timeout=deadline_s)
        report["feed_s"] = round(time.perf_counter() - t0, 3)
    report["step_retraces_in_window"] = _engine_compiles(dep.rt) - c0
    compiled = clog.since(m0)
    report["backend_compiles_in_window"] = len(compiled)
    if report["step_retraces_in_window"] or compiled:
        fails.append(f"{label}: compiled inside the fed window: "
                     f"{report['step_retraces_in_window']} step retraces, "
                     f"backend compiles {[n for n, _ in compiled]}")


def _warm(dep: _Deployment, traffic: _Traffic, n_more: int, sizes: Sizes,
          report: dict) -> None:
    """Warm-up: compile the full-width step ahead of time (a failure is
    fatal), then count what the FIRST real batch still compiles — the
    answer to whether `aot_warm`'s lower().compile() fills the dispatch
    cache — then feed `n_more` frames in one go, so that whatever compiles
    only under traffic (read-back packing, a staged superstep's scan) has
    compiled before a fed window opens."""
    clog = _CompileLog.get()
    with _Deadline(f"{dep.name}:warmup", sizes.warmup_deadline_s):
        m0 = clog.mark()
        t0 = time.perf_counter()
        warmed = dep.rt.warmup(tuple(sorted(
            {j.batch_size for j in dep.rt.junctions.values()})))
        report["aot_warmup_s"] = round(time.perf_counter() - t0, 3)
        report["aot_compiles"] = dict(warmed)
        if warmed.failures:
            raise SmokeError(f"warm-up failed to compile: "
                             f"{ {k: repr(v) for k, v in warmed.failures.items()} }")
        frames = traffic.make(1 + n_more)
        c0, m1 = _engine_compiles(dep.rt), clog.mark()
        t0 = time.perf_counter()
        dep.post_frames([traffic.bodies[frames[0]]])
        dep.rt.drain(timeout=sizes.warmup_deadline_s)
        first = clog.since(m1)
        report["first_batch_after_warmup"] = {
            "seconds": round(time.perf_counter() - t0, 3),
            "step_retraces": _engine_compiles(dep.rt) - c0,
            "backend_compiles": [n for n, _ in first],
            "backend_compile_s": round(sum(s for _, s in first), 3),
        }
        dep.post_frames([traffic.bodies[i] for i in frames[1:]])
        dep.rt.drain(timeout=sizes.warmup_deadline_s)
        report["warm_feed_s"] = round(time.perf_counter() - t0, 3)
        # every program built during warm-up, from the compiler or from the
        # persistent cache (a hit is timed too: it is the retrieval)
        built = clog.since(m0)
        report["warmup_programs"] = {
            "count": len(built),
            "seconds": round(sum(s for _, s in built), 3),
            "slowest": [[n, round(s, 3)] for n, s in
                        sorted(built, key=lambda b: -b[1])[:3]]}


# ------------------------------------------------------------------ the run


def device_identity() -> dict:
    """What jax runs on, as jax reports it, plus the installed versions."""
    from importlib import metadata

    import jax
    dev = jax.devices()[0]

    def ver(pkg: str):
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return None

    return {"device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "versions": {"jax": ver("jax"), "jaxlib": ver("jaxlib"),
                         "libtpu": ver("libtpu")}}


def run_smoke(sizes: Sizes, require_platform: str, seed: int = 0) -> dict:
    """The smoke's body. Refuses to run unless jax's first device is on
    `require_platform` (SmokeError, before anything is built). Returns the
    report; `report["ok"]` is true only if every phase ran and every check
    passed, and `report["failures"]` says what did not."""
    ident = device_identity()
    if ident["device"]["platform"] != require_platform:
        raise SmokeError(
            f"requires platform {require_platform!r}, but jax.devices()[0] "
            f"is {ident['device']} — refusing to carry on there")
    import jax

    import siddhi_tpu.native
    from siddhi_tpu.io import wire
    from siddhi_tpu.util.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    clog = _CompileLog.get()
    hits0, misses0, reqs0 = \
        clog.cache_hits, clog.cache_misses, clog.cache_requests
    trap = _EngineLogTrap()
    engine_log = logging.getLogger("siddhi_tpu")
    engine_log.addHandler(trap)
    fails: list = []
    phases: dict = {"A": {}, "B": {}, "C": {}}
    report = {"ok": False, **ident, "seed": seed,
              "sizes": dataclasses.asdict(sizes),
              "native": siddhi_tpu.native.available(), "phases": phases}
    if not report["native"] and not siddhi_tpu.native._DISABLED:
        fails.append("native module did not load (set SIDDHI_NATIVE=0 to "
                     "run the Python host path on purpose)")
    rng = np.random.default_rng(seed)
    symbols = np.array([f"SYM{i:07d}" for i in range(sizes.keys)],
                       dtype=object)
    window = sizes.window
    dep = None
    try:
        # ---- phases A and B share one runtime, as a server would
        dep = _Deployment("SmokeIngress", sizes, superstep=False)
        plan = wire.schema_plan(dep.rt.junctions["TradeStream"].definition)
        traffic = _Traffic(rng, symbols, plan, sizes)
        _warm(dep, traffic, sizes.warm_frames, sizes, phases["A"])
        if "TradeStream" not in \
                dep.rt.statistics_report()["ingress_pipeline"]:
            fails.append("the ingress pipeline did not engage for "
                         "TradeStream (start_async fell back)")
        off = _state_off_device(dep.rt, require_platform)
        if off:
            fails.append(f"query state not on a {require_platform} "
                         f"device: {off}")

        frames = traffic.make(sizes.frames_a)
        _fed_window(dep, "A", lambda: dep.post_frames(
            [traffic.bodies[i] for i in frames]),
            sizes.phase_deadline_s, phases["A"], fails)
        sym, price, ts = traffic.columns()
        phases["A"].update(_compare(
            dep.emitted(), symbols, np.arange(ts.size), sym, price, ts,
            window, "A", fails))
        phases["A"]["events_in"] = int(ts.size)

        b = sizes.batch
        per = [traffic.make(sizes.frames_b)
               for _ in range(sizes.producers_b)]

        def feed_b() -> None:
            errors: list = []

            def producer(mine) -> None:
                try:
                    dep.post_frames([traffic.bodies[i] for i in mine])
                except Exception as e:  # noqa: BLE001 — re-raised below
                    errors.append(e)

            threads = [threading.Thread(target=producer, args=(mine,),
                                        name=f"smoke-producer-{p}")
                       for p, mine in enumerate(per)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]

        _fed_window(dep, "B", feed_b, sizes.phase_deadline_s, phases["B"],
                    fails)
        sym, price, ts = traffic.columns()
        out = dep.emitted()
        cons, order = _conservation(
            dep, out[0], [(mine[0] * b, (mine[-1] + 1) * b) for mine in per],
            price, window, fails)
        phases["B"].update(cons)
        # the reference, run in the arrival order the output reveals; the
        # events of the last, unfilled window did not come out and are not
        # in `order`, which is exactly what the reference leaves out too
        phases["B"]["compare"] = _compare(
            out, symbols, order, sym, price, ts, window, "B", fails)
        phases["B"]["events_in"] = int(ts.size)

        # ---- the three read endpoints, once each
        stats = json.loads(dep.get(f"/siddhi-apps/{dep.name}/statistics"))
        ready = json.loads(dep.get("/ready"))
        metrics = dep.get("/metrics").decode()
        report["endpoints"] = {
            "statistics_has_pipeline":
                "TradeStream" in stats.get("ingress_pipeline", {}),
            "ready": bool(ready.get("ready")),
            "metrics_lines": len(metrics.splitlines())}
        if not all(report["endpoints"].values()):
            fails.append(f"an endpoint answered wrong: {report['endpoints']}")
        dep.close()
        dep = None

        # ---- phase C: the lax.scan superstep with on-device compaction
        dep = _Deployment("SmokeSuperstep", sizes, superstep=True)
        traffic = _Traffic(rng, symbols, plan, sizes)
        _warm(dep, traffic, sizes.superstep_k, sizes, phases["C"])
        frames = traffic.make(sizes.frames_c)
        _fed_window(dep, "C", lambda: dep.post_frames(
            [traffic.bodies[i] for i in frames]),
            sizes.phase_deadline_s, phases["C"], fails)
        sym, price, ts = traffic.columns()
        phases["C"].update(_compare(
            dep.emitted(), symbols, np.arange(ts.size), sym, price, ts,
            window, "C", fails))
        phases["C"]["events_in"] = int(ts.size)
        pipe = dep.pipeline_stats()
        phases["C"]["supersteps_dispatched"] = \
            pipe.get("supersteps_dispatched", 0)
        phases["C"]["superstep_decline"] = pipe.get("superstep_decline")
        if not phases["C"]["supersteps_dispatched"] \
                or phases["C"]["superstep_decline"]:
            fails.append(f"C: supersteps did not engage: dispatched="
                         f"{phases['C']['supersteps_dispatched']}, decline="
                         f"{phases['C']['superstep_decline']!r}")
        off = _state_off_device(dep.rt, require_platform)
        if off:
            fails.append(f"C: query state not on a {require_platform} "
                         f"device: {off}")
    except Exception as e:  # noqa: BLE001 — a phase that cannot finish
        import traceback
        traceback.print_exc()
        fails.append(f"aborted: {e!r}")
    finally:
        if dep is not None:
            try:
                dep.close()
            except Exception as e:  # noqa: BLE001 — already failing
                fails.append(f"teardown: {e!r}")
        engine_log.removeHandler(trap)
    fails.extend(f"engine log: {m}" for m in trap.tripped)

    mem = jax.devices()[0].memory_stats() or {}
    report.update({
        "compile_cache": {
            "dir": cache_dir,
            "hits": clog.cache_hits - hits0,
            "misses": clog.cache_misses - misses0,
            "requests": clog.cache_requests - reqs0},
        # building programs (all of it in warm-up) apart from feeding
        "compile_s": round(sum(
            phases[p].get("warmup_programs", {}).get("seconds", 0.0)
            for p in "AC"), 3),
        "feed_s": {p: phases[p].get("feed_s") for p in "ABC"},
        "compiles_in_fed_windows": sum(
            phases[p].get("step_retraces_in_window", 0)
            + phases[p].get("backend_compiles_in_window", 0) for p in "ABC"),
        # per runtime, and B's count includes A's (they share one)
        "events_in": sum(phases[p].get("events_in", 0) for p in "BC"),
        "rows_out": sum(phases[p].get("rows_out", 0) for p in "BC"),
        "exact_match": bool(
            phases["A"].get("exact_match")
            and phases["B"].get("compare", {}).get("exact_match")
            and phases["C"].get("exact_match")),
        "conserved": bool(phases["B"].get("conserved")),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "failures": fails,
    })
    report["ok"] = not fails
    report["claim"] = None  # a smoke run measures nothing anyone may quote
    return report


def verdict_line(report: dict) -> str:
    """The last line of standard output: whether every check passed and the
    device as jax reports it, and no other key — the form the chip check
    reads. Everything else the run found is in the report line before it."""
    dev = report["device"]
    return json.dumps({"ok": bool(report["ok"]), "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the generated symbols, prices and volumes")
    args = ap.parse_args(argv)
    faulthandler.enable()
    # the engine's log goes to stderr as well as into the smoke's trap
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(levelname)s %(name)s: %(message)s")
    try:
        ident = device_identity()
    except Exception as e:  # noqa: BLE001 — jax could not start a backend
        print(f"chip_smoke: jax found no usable device: {e!r}",
              file=sys.stderr)
        return 2
    print(f"[chip_smoke] {json.dumps(ident)}", file=sys.stderr, flush=True)
    try:
        # the command line always requires the chip; only tests pass
        # another platform to run_smoke
        report = run_smoke(Sizes(), "tpu", seed=args.seed)
    except SmokeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    for f in report["failures"]:
        print(f"[chip_smoke] FAILED: {f}", file=sys.stderr)
    print(json.dumps(report))
    print(verdict_line(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
