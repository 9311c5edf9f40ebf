"""Shape-bucketed compile cache and AOT warmup.

Covers the round-6 perf tentpole:
- junctions pad partial micro-batches to power-of-two lane buckets, so a
  shape-polymorphic query step compiles at most log2(max_batch)+1 variants
  (visible through the new per-query compile counter in Statistics);
- padded (bucketed) execution is bit-identical to full-capacity execution;
- AOT warmup precompiles the whole ladder at start().
"""

from __future__ import annotations

import math

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import dtypes
from siddhi_tpu.errors import SiddhiAppCreationError

FILTER_APP = """
define stream S (symbol string, price double, volume long);
@info(name = 'q')
from S[700.0 > price]
select symbol, price
insert into Out;
"""


@pytest.fixture
def buckets_on():
    prev = dtypes.config.shape_buckets
    dtypes.config.shape_buckets = True
    yield
    dtypes.config.shape_buckets = prev


def _feed_and_collect(app, sizes, *, batch_size=8192, **kw):
    rt = SiddhiManager().create_siddhi_app_runtime(
        app, batch_size=batch_size, **kw)
    got = []
    out_id = next(ln.split("insert into ")[1].split(";")[0].strip()
                  for ln in app.splitlines() if "insert into" in ln)
    rt.add_callback(out_id, lambda evs: got.extend(
        (e.data, e.is_expired) for e in evs))
    rt.start()
    h = rt.get_input_handler("S")
    ts = 1
    for n in sizes:
        rows = [(f"S{i % 50}", float(i % 900), i) for i in range(n)]
        h.send_batch(rows, timestamps=list(range(ts, ts + n)))
        ts += n
        rt.flush()
    compiles = dict(rt.statistics.compiles)
    widths = {q: list(w) for q, w in rt.statistics.compile_widths.items()}
    rt.shutdown()
    return got, compiles, widths


class TestBucketLadder:
    def test_bucket_capacity_math(self):
        assert dtypes.bucket_capacity(0, 8192) == dtypes.config.min_bucket
        assert dtypes.bucket_capacity(1, 8192) == 16
        assert dtypes.bucket_capacity(16, 8192) == 16
        assert dtypes.bucket_capacity(17, 8192) == 32
        assert dtypes.bucket_capacity(8191, 8192) == 8192
        assert dtypes.bucket_capacity(9000, 8192) == 8192
        # non-power-of-two capacity stays the top rung
        assert dtypes.bucket_capacity(200, 200) == 200
        assert dtypes.bucket_ladder(200)[-1] == 200

    def test_ladder_is_log2_bounded(self):
        for cap in (16, 100, 256, 8192, 131072):
            ladder = dtypes.bucket_ladder(cap)
            assert ladder[-1] == cap
            assert len(ladder) <= int(math.log2(max(cap, 2))) + 1
            assert list(ladder) == sorted(set(ladder))


class TestCompileCountStability:
    """Acceptance: one query fed batches of sizes {1, 7, 100, 8192}
    compiles <= log2(max_batch)+1 variants, bit-identical to unpadded."""

    SIZES = (1, 7, 100, 8192, 7, 1, 8192, 100)

    def test_filter_query_log2_bound_and_bit_identity(self, buckets_on):
        got_b, compiles_b, widths_b = _feed_and_collect(
            FILTER_APP, self.SIZES)
        bound = int(math.log2(8192)) + 1
        assert 0 < compiles_b["q"] <= bound
        # repeats of a seen size never retrace: distinct widths == compiles
        assert len(set(widths_b["q"])) == compiles_b["q"]

        dtypes.config.shape_buckets = False
        got_u, compiles_u, _ = _feed_and_collect(FILTER_APP, self.SIZES)
        assert compiles_u["q"] == 1  # always padded to full capacity
        assert got_b == got_u  # bit-identical decode (values + order)

    def test_sliding_window_query_bit_identity(self, buckets_on):
        app = """
        define stream S (symbol string, price double, volume long);
        @info(name = 'q')
        from S#window.time(60 sec)
        select symbol, distinctCount(symbol) as d
        insert into Out;
        """
        sizes = (1, 7, 100, 256, 3)
        got_b, compiles_b, _ = _feed_and_collect(app, sizes, batch_size=256)
        assert 0 < compiles_b["q"] <= int(math.log2(256)) + 1
        dtypes.config.shape_buckets = False
        got_u, _, _ = _feed_and_collect(app, sizes, batch_size=256)
        assert got_b == got_u

    def test_shape_baked_window_pads_to_one_compile(self, buckets_on):
        # lengthBatch is NOT shape-polymorphic: the runtime pads bucketed
        # deliveries back to full capacity — exactly one compile, same
        # results as with bucketing disabled
        app = """
        define stream S (symbol string, price double, volume long);
        @info(name = 'q')
        from S#window.lengthBatch(5)
        select symbol, sum(volume) as total
        insert into Out;
        """
        sizes = (1, 7, 100, 3, 13)
        got_b, compiles_b, _ = _feed_and_collect(app, sizes, batch_size=128)
        assert compiles_b["q"] == 1
        dtypes.config.shape_buckets = False
        got_u, _, _ = _feed_and_collect(app, sizes, batch_size=128)
        assert got_b == got_u


class TestAotWarmup:
    def test_start_precompiles_ladder_then_traffic_adds_none(
            self, buckets_on):
        rt = SiddhiManager().create_siddhi_app_runtime(
            FILTER_APP, batch_size=1024, aot_warmup=True)
        rt.start()
        ladder = dtypes.bucket_ladder(1024)
        assert rt.statistics.compiles["q"] == len(ladder)
        assert sorted(rt.statistics.compile_widths["q"]) == sorted(ladder)
        h = rt.get_input_handler("S")
        for n in (1, 5, 1000, 1024):
            h.send_batch([(f"S{i}", 1.0, i) for i in range(n)])
            rt.flush()
        assert rt.statistics.compiles["q"] == len(ladder)  # zero retraces
        rt.shutdown()

    def test_warmup_method_returns_compile_counts(self, buckets_on):
        rt = SiddhiManager().create_siddhi_app_runtime(
            FILTER_APP, batch_size=256)
        fresh = rt.warmup()
        assert fresh["q"] == len(dtypes.bucket_ladder(256))
        assert rt.warmup()["q"] == 0  # second warmup: all cached

    def test_warmup_does_not_disturb_live_state(self, buckets_on):
        app = """
        define stream S (symbol string, price double, volume long);
        @info(name = 'q')
        from S#window.lengthBatch(3)
        select symbol, sum(volume) as total
        insert into Out;
        """
        rt = SiddhiManager().create_siddhi_app_runtime(app, batch_size=64)
        got = []
        rt.add_callback("Out", lambda evs: got.extend(
            e.data for e in evs if not e.is_expired))
        rt.start()
        h = rt.get_input_handler("S")
        h.send_batch([("a", 1.0, 1), ("a", 1.0, 2)])
        rt.flush()
        rt.warmup()  # state copies only: the partial window must survive
        h.send_batch([("a", 1.0, 3)])
        rt.flush()
        assert [d[1] for d in got][-3:] == [1, 3, 6]
        rt.shutdown()


class TestStatisticsSurface:
    def test_report_exposes_compiles_and_step_hist(self, buckets_on):
        rt = SiddhiManager().create_siddhi_app_runtime(
            FILTER_APP, batch_size=64)
        rt.set_statistics_level("DETAIL")
        rt.start()
        h = rt.get_input_handler("S")
        h.send_batch([("a", 1.0, 1)])
        rt.flush()
        rep = rt.statistics_report()
        assert rep["compiles"]["q"] >= 1
        assert rep["compile_widths"]["q"]
        hist = rep["step_time_hist_us"]["q"]
        assert sum(hist.values()) >= 1
        assert all(b > 0 and (b & (b - 1)) == 0 for b in hist)  # pow2 buckets
        rt.shutdown()


class TestSetProjectionProvenance:
    """ADVICE r5: sizeOfSet over an ORDINARY long column must raise instead
    of silently forwarding the value; provenance-marked forwarded unionSet
    columns keep working (chained stream + insert-into table)."""

    def test_plain_long_rejected(self):
        app = ("define stream S (sym string, n long);\n"
               "@info(name='fw') from S select sym, n insert into Mid;\n"
               "@info(name='rd') from Mid select sizeOfSet(n) as c "
               "insert into Out;")
        with pytest.raises(SiddhiAppCreationError, match="sizeOfSet"):
            SiddhiManager().create_siddhi_app_runtime(app, batch_size=8)

    def test_forwarded_union_set_still_readable(self):
        app = ("define stream S (sym string);\n"
               "@info(name='fw') from S select unionSet(sym) as s "
               "insert into Mid;\n"
               "@info(name='rd') from Mid select sizeOfSet(s) as c "
               "insert into Out;")
        rt = SiddhiManager().create_siddhi_app_runtime(app, batch_size=8)
        got = []
        rt.add_callback("Out", lambda evs: got.extend(
            e.data[0] for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for x in ("a", "b", "a", "c"):
            h.send((x,))
            rt.flush()
        assert got == [1, 2, 2, 3]
        rt.shutdown()
