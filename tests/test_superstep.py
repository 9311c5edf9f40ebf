"""Device-resident supersteps (core/superstep.py + the ingress feeder's
K-staging).

The correctness contract under test: with `@app:superstep(k=K)` (or
SIDDHI_SUPERSTEP_K), the feeder stages K ring chunks into one device
chunk and runs the whole eligible sub-plan as a single `lax.scan` with
on-device output compaction — and every observable surface (sink blocks,
timestamps, dtypes, expired flags, telemetry traces, statistics) is
BIT-IDENTICAL to the same app run per-batch at K=1. Equality below is
`np.testing.assert_array_equal`, not approx: the scan replays the exact
K=1 step function over the same padded lanes, so even float accumulator
order is unchanged.

Plus the operational surface: the decline taxonomy (ineligible plans fall
back loudly to per-batch, once, with the reason in stats_snapshot), the
wide arm of `stable_argsort_bounded` against numpy's stable argsort,
telemetry batch attribution under K>1
(one trace per inner batch, stages additive, `superstep_k` stamped), and
the pure-Python-ring subprocess parity run (SIDDHI_NATIVE=0)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BS = 64
K = 4
N_ROWS = 2048  # 32 full chunks at BS=64 -> 8 supersteps at K=4

ASYNC_HDR = "@Async(buffer.size='64', workers='2')\n" \
            "define stream TradeStream (symbol string, price double, " \
            "volume long);\n"

APP_FILTER = (
    "@app:name('SSF{tag}')\n{ann}" + ASYNC_HDR +
    "@info(name='filt') from TradeStream[price < 700.0] "
    "select symbol, price, volume insert into OutStream;")

APP_CHAIN = (
    "@app:name('SSC{tag}')\n{ann}" + ASYNC_HDR +
    "@info(name='filt') from TradeStream[price < 700.0] "
    "select symbol, price, volume insert into MidStream;\n"
    "@info(name='agg') from MidStream#window.lengthBatch(50) "
    "select symbol, sum(price) as total, avg(price) as avgPrice "
    "group by symbol insert into OutStream;")

APP_SLIDING = (
    "@app:name('SSW{tag}')\n{ann}" + ASYNC_HDR +
    "@info(name='slide') from TradeStream#window.length(40) "
    "select symbol, sum(price) as s, count() as n "
    "insert into OutStream;")

APP_DISTINCT = (
    "@app:name('SSD{tag}')\n{ann}" + ASYNC_HDR +
    "@info(name='dq') from TradeStream#window.length(64) "
    "select distinctCount(symbol) as d insert into OutStream;")

APP_JOIN = (
    "@app:name('SSJ{tag}')\n{ann}" + ASYNC_HDR +
    "define stream QuoteStream (symbol string, bid double);\n"
    "@info(name='jq') from TradeStream#window.length(32) join "
    "QuoteStream#window.length(16) "
    "on TradeStream.symbol == QuoteStream.symbol "
    "select TradeStream.symbol as symbol, TradeStream.price as price, "
    "QuoteStream.bid as bid insert into OutStream;")


def _rows(n, seed=11):
    rng = np.random.default_rng(seed)
    ks = rng.integers(1, 12, n)
    ps = rng.uniform(1.0, 1000.0, n)
    vs = rng.integers(1, 1000, n)
    return [(f"S{int(k)}", float(p), int(v)) for k, p, v in zip(ks, ps, vs)]


def _with_k(app_tmpl, k):
    if k <= 1:
        return app_tmpl.format(tag="K1", ann="")
    return app_tmpl.format(tag=f"K{k}",
                           ann=f"@app:superstep(k='{k}')\n")


def _capture(app, feed):
    """Run `app`, collect OutStream blocks columnar, return
    (blocks, pipeline stats_snapshot)."""
    rt = SiddhiManager().create_siddhi_app_runtime(app)
    blocks = []
    rt.add_callback("OutStream", lambda b: blocks.append(
        (b.timestamps.copy(),
         {k: v.copy() for k, v in b.columns.items()},
         b.is_expired.copy())), columnar=True)
    rt.start()
    try:
        feed(rt)
        rt.drain()
        snap = rt.junctions["TradeStream"]._pipeline.stats_snapshot()
    finally:
        rt.shutdown()
    return blocks, snap


def _feed_trades(rt):
    h = rt.get_input_handler("TradeStream")
    h.send_batch(_rows(N_ROWS),
                 timestamps=np.arange(1, N_ROWS + 1, dtype=np.int64))


def _assert_blocks_identical(got, want):
    assert len(got) == len(want), (len(got), len(want))
    for (gt, gc, ge), (wt, wc, we) in zip(got, want):
        np.testing.assert_array_equal(gt, wt)
        np.testing.assert_array_equal(ge, we)
        assert gc.keys() == wc.keys()
        for k in wc:
            assert gc[k].dtype == wc[k].dtype, k
            np.testing.assert_array_equal(gc[k], wc[k], err_msg=k)


def _parity(app_tmpl, feed=_feed_trades):
    want, s1 = _capture(_with_k(app_tmpl, 1), feed)
    got, sk = _capture(_with_k(app_tmpl, K), feed)
    # the superstep actually engaged — we are not comparing K=1 to K=1
    assert sk["supersteps_dispatched"] > 0, sk
    assert sk["superstep_decline"] is None, sk
    assert sk["superstep_k"] == K
    assert s1["supersteps_dispatched"] == 0
    _assert_blocks_identical(got, want)
    return got


class TestSuperstepParity:
    """Bit-identical output, K=4 vs K=1, across the plan shapes the scan
    supports: plain filter, chained group-by, sliding window, a custom
    aggregate (distinctCount maintenance replays per inner batch), and a
    stream-stream join side."""

    def test_filter(self):
        blocks = _parity(APP_FILTER)
        assert sum(len(b[0]) for b in blocks) > 0

    def test_chained_groupby(self):
        _parity(APP_CHAIN)

    def test_sliding_window(self):
        _parity(APP_SLIDING)

    def test_distinct_count(self):
        _parity(APP_DISTINCT)

    def test_join_side(self):
        def feed(rt):
            q = rt.get_input_handler("QuoteStream")
            for i in range(12):
                q.send((f"S{i % 12 + 1}", 10.0 + i))
            rt.flush()
            _feed_trades(rt)

        _parity(APP_JOIN, feed)

    def test_env_knob_overrides_annotation(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_SUPERSTEP_K", str(K))
        want, _ = _capture(_with_k(APP_FILTER, 1).replace("SSFK1",
                                                          "SSFenvW"),
                           _feed_trades)
        monkeypatch.undo()
        monkeypatch.setenv("SIDDHI_SUPERSTEP_K", "1")
        got, snap = _capture(_with_k(APP_FILTER, K).replace("SSFK4",
                                                            "SSFenvG"),
                             _feed_trades)
        # env K=1 overrides the annotation's k=4: no supersteps ran
        assert snap["supersteps_dispatched"] == 0
        _assert_blocks_identical(got, want)

    def test_python_ring_subprocess_parity(self, tmp_path):
        """SIDDHI_NATIVE=0 forces the pure-Python ingress ring (decided
        at import time, hence the subprocess): same superstep parity
        oracle on the chained group-by app."""
        script = tmp_path / "ss_parity_py.py"
        script.write_text(
            "import sys; sys.path.insert(0, %r)\n" % REPO
            + "from siddhi_tpu.util.platform import force_cpu_platform\n"
            "force_cpu_platform(1)\n"
            "from tests.test_superstep import APP_CHAIN, _parity\n"
            "from siddhi_tpu.core.ingress import _PyColRing\n"
            "import siddhi_tpu.core.ingress as ing\n"
            "blocks = _parity(APP_CHAIN)\n"
            "print('SS-PARITY-PY OK', len(blocks))\n")
        env = {**os.environ, "SIDDHI_NATIVE": "0", "JAX_PLATFORMS": "cpu"}
        env.pop("SIDDHI_SUPERSTEP_K", None)
        p = subprocess.run([sys.executable, str(script)], env=env,
                           capture_output=True, text=True, timeout=420)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        assert "SS-PARITY-PY OK" in p.stdout


class TestSuperstepDecline:
    """Ineligible plans fall back to per-batch dispatch — loudly, once,
    with the reason surfaced in stats_snapshot — and still produce
    correct output."""

    def test_non_query_ingress_receiver_declines(self):
        app = _with_k(APP_FILTER, K).replace("SSFK4", "SSFdecl")
        rt = SiddhiManager().create_siddhi_app_runtime(app)
        out, taps = [], []
        rt.add_callback("OutStream",
                        lambda b: out.append(b.count), columnar=True)
        # a callback on the INGRESS stream itself is a non-step receiver:
        # the scan cannot absorb it, so the whole plan declines
        rt.add_callback("TradeStream",
                        lambda b: taps.append(b.count), columnar=True)
        rt.start()
        try:
            _feed_trades(rt)
            rt.drain()
            snap = rt.junctions["TradeStream"]._pipeline.stats_snapshot()
        finally:
            rt.shutdown()
        assert snap["supersteps_dispatched"] == 0
        assert snap["superstep_decline"] is not None
        assert sum(taps) == N_ROWS  # fallback delivered everything
        assert sum(out) > 0

    def test_k1_never_builds_a_runner(self):
        _, snap = _capture(_with_k(APP_FILTER, 1), _feed_trades)
        assert snap["superstep_k"] == 1
        assert snap["supersteps_dispatched"] == 0
        assert snap["superstep_decline"] is None


class TestDeviceSortParity:
    """`stable_argsort_bounded` at a width that takes its wide arm (the
    packed-key `lax.sort` on the CPU backend): bit-identical to numpy's
    stable argsort on seeded heavy-tie keys."""

    LANES = 16384  # above _PACKED_SORT_MIN_LANES -> wide arm

    def _keys(self, seed):
        rng = np.random.default_rng(seed)
        # heavy ties: 50 distinct values over 16384 lanes
        return rng.integers(0, 50, self.LANES).astype(np.int32)

    def test_packed_sort_is_stable(self):
        from siddhi_tpu.ops.search import stable_argsort_bounded
        for seed in (1, 2, 3):
            x = self._keys(seed)
            got = np.asarray(stable_argsort_bounded(x))
            want = np.argsort(x, kind="stable").astype(np.int32)
            np.testing.assert_array_equal(got, want)

    def test_batched_rows_stable(self):
        from siddhi_tpu.ops.search import stable_argsort_bounded
        rng = np.random.default_rng(9)
        x = rng.integers(0, 8, (4, self.LANES)).astype(np.int32)
        got = np.asarray(stable_argsort_bounded(x))
        want = np.argsort(x, axis=-1, kind="stable").astype(np.int32)
        np.testing.assert_array_equal(got, want)


class TestSuperstepTelemetry:
    """Under K>1, batch attribution keeps per-batch semantics: one trace
    per inner batch (same count, sizes, and monotone IDs as K=1), each
    stamped with `superstep_k`, and the scan's device time split across
    them so stage totals stay additive."""

    # small feed: 8 chunks -> 2 supersteps, so every trace (ingress +
    # chained streams) fits in the RECENT_RING=64 deque without eviction
    N_TELE = 8 * BS

    def _traces(self, app):
        rt = SiddhiManager().create_siddhi_app_runtime(app)
        rt.add_callback("OutStream", lambda b: None, columnar=True)
        rt.start()
        try:
            h = rt.get_input_handler("TradeStream")
            h.send_batch(_rows(self.N_TELE),
                         timestamps=np.arange(1, self.N_TELE + 1,
                                              dtype=np.int64))
            rt.drain()
            tele = rt.ctx.telemetry
            traces = [t for t in tele.recent_summaries()
                      if t["stream"] == "TradeStream"]
            snap = rt.junctions["TradeStream"]._pipeline.stats_snapshot()
        finally:
            rt.shutdown()
        return traces, snap

    def test_one_trace_per_inner_batch(self):
        traces, snap = self._traces(_with_k(APP_CHAIN, K))
        assert snap["supersteps_dispatched"] > 0
        ss = [t for t in traces if t.get("superstep_k") == K]
        assert ss, "no superstep-stamped traces retired"
        # each superstep retires exactly K inner-batch traces
        assert len(ss) == snap["supersteps_dispatched"] * K
        assert all(t["batch_size"] == BS for t in ss)
        ids = [t["batch_id"] for t in traces]
        assert ids == sorted(ids) and len(set(ids)) == len(ids)
        # conservation: every row is attributed to exactly one trace
        assert sum(t["batch_size"] for t in traces) == self.N_TELE

    def test_stages_additive_and_queries_attributed(self):
        traces, _ = self._traces(_with_k(APP_CHAIN, K))
        ss = [t for t in traces if t.get("superstep_k") == K]
        assert ss
        # the scan's device span was split across inner batches
        assert sum(t["stages_ms"]["device"] for t in ss) > 0
        assert any("filt" in t["queries"] for t in ss)

    def test_k1_traces_carry_no_superstep_key(self):
        traces, _ = self._traces(_with_k(APP_CHAIN, 1))
        assert traces
        assert all("superstep_k" not in t for t in traces)


class TestSuperstepStatistics:
    """@app:statistics stays supported under supersteps: throughput and
    latency accounting match K=1 (in-scan chain counts feed track_in)."""

    def test_statistics_parity(self):
        tmpl = APP_CHAIN.replace("@app:name('SSC{tag}')",
                                 "@app:name('SSS{tag}')\n"
                                 "@app:statistics('true')")
        reps = {}
        for k in (1, K):
            rt = SiddhiManager().create_siddhi_app_runtime(_with_k(tmpl, k))
            rt.add_callback("OutStream", lambda b: None, columnar=True)
            rt.start()
            try:
                _feed_trades(rt)
                rt.drain()
                snap = rt.junctions["TradeStream"]._pipeline \
                    .stats_snapshot()
                if k > 1:
                    assert snap["supersteps_dispatched"] > 0, snap
                reps[k] = rt.statistics_report()["events_in"]
            finally:
                rt.shutdown()
        assert reps[1] == reps[K]
