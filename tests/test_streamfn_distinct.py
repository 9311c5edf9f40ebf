"""Stream-function FROM chains + distinctCount aggregator tests (reference:
query/streamfunction/Pol2CartTestCase, query/aggregator/DistinctCountTestCase
— incl. the BASELINE config-3 shape: sliding distinctCount)."""

import pytest

from siddhi_tpu import SiddhiManager

S = "define stream S (symbol string, theta double, rho double, v long);\n"


def build(app, batch_size=8):
    rt = SiddhiManager().create_siddhi_app_runtime(app, batch_size=batch_size)
    rt.start()
    return rt


def q_callback(rt, name):
    got = []
    rt.add_query_callback(name, lambda ts, i, r: got.extend(i or []))
    return got


class TestStreamFunctions:
    def test_pol2cart_adds_columns(self):
        rt = build(
            S + "@info(name='q') from S#pol2Cart(theta, rho) "
            "select symbol, x, y insert into Out;")
        got = q_callback(rt, "q")
        rt.get_input_handler("S").send(("A", 0.0, 5.0, 1))
        rt.get_input_handler("S").send(("B", 90.0, 2.0, 1))
        rt.flush()
        assert got[0].data == ("A", pytest.approx(5.0), pytest.approx(0.0, abs=1e-6))
        assert got[1].data == ("B", pytest.approx(0.0, abs=1e-6), pytest.approx(2.0))

    def test_stream_fn_feeds_window_aggregate(self):
        rt = build(
            S + "@info(name='q') from S#pol2Cart(theta, rho)#window.lengthBatch(2) "
            "select symbol, sum(x) as sx insert into Out;")
        got = q_callback(rt, "q")
        h = rt.get_input_handler("S")
        h.send(("A", 0.0, 3.0, 1))   # x=3
        h.send(("A", 0.0, 4.0, 1))   # x=4
        rt.flush()
        assert got[-1].data[1] == pytest.approx(7.0)

    def test_select_star_includes_new_attrs(self):
        rt = build(
            S + "@info(name='q') from S#pol2Cart(theta, rho) "
            "select * insert into Out;")
        got = q_callback(rt, "q")
        rt.get_input_handler("S").send(("A", 0.0, 5.0, 9))
        rt.flush()
        # original attrs + x, y
        assert len(got[0].data) == 6


class TestDistinctCount:
    APP = ("define stream T (user string, page string, v long);\n"
           "@info(name='q') from T{window} "
           "select user, distinctCount(page) as pages "
           "group by user insert into Out;")

    def test_plain_distinct_count(self):
        rt = build(self.APP.format(window=""))
        got = q_callback(rt, "q")
        h = rt.get_input_handler("T")
        for row in [("u1", "a", 1), ("u1", "b", 1), ("u1", "a", 1),
                    ("u2", "a", 1), ("u1", "c", 1)]:
            h.send(row)
        rt.flush()
        per_lane = [(e.data[0], e.data[1]) for e in got]
        assert per_lane == [("u1", 1), ("u1", 2), ("u1", 2), ("u2", 1), ("u1", 3)]

    def test_sliding_window_removal(self):
        # BASELINE config 3 shape: sliding length window — values leaving the
        # window decrement the distinct count exactly
        rt = build(self.APP.format(window="#window.length(2)"))
        got = q_callback(rt, "q")
        h = rt.get_input_handler("T")
        for row in [("u1", "a", 1), ("u1", "b", 1), ("u1", "c", 1)]:
            h.send(row)
            rt.flush()
        # after c arrives, a expired: distinct = {b, c} = 2
        currents = [e.data[1] for e in got if not e.is_expired]
        assert currents[-1] == 2

    def test_duplicate_survives_partial_expiry(self):
        rt = build(self.APP.format(window="#window.length(2)"))
        got = q_callback(rt, "q")
        h = rt.get_input_handler("T")
        for row in [("u1", "a", 1), ("u1", "a", 1), ("u1", "b", 1)]:
            h.send(row)
            rt.flush()
        # window holds [a, b] after first a expired — 'a' still present once
        currents = [e.data[1] for e in got if not e.is_expired]
        assert currents == [1, 1, 2]

    @pytest.mark.parametrize("flush_each", [True, False],
                             ids=["a_batch_an_event", "one_batch"])
    def test_a_value_that_leaves_and_returns(self, flush_each):
        # the (u1, a) pair's count goes 1 -> 0 -> 1 -> 0 -> 1 (inside ONE
        # batch without the flushes): each transition reads the whole
        # post-update count of the hashed pair table
        rt = build(self.APP.format(window="#window.length(2)"))
        got = q_callback(rt, "q")
        h = rt.get_input_handler("T")
        pages = ["a", "b", "c", "a", "b", "c", "a", "a"]
        for page in pages:
            h.send(("u1", page, 1))
            if flush_each:
                rt.flush()
        rt.flush()
        currents = [e.data[1] for e in got if not e.is_expired]
        assert currents == [len(set(pages[max(0, i - 1):i + 1]))
                            for i in range(len(pages))]

    def test_float_values_distinct_by_bits(self):
        app = ("define stream T (user string, price double, v long);\n"
               "@info(name='q') from T select user, distinctCount(price) as n "
               "group by user insert into Out;")
        rt = build(app)
        got = q_callback(rt, "q")
        h = rt.get_input_handler("T")
        for p in [1.2, 1.9, 2.5, 1.2]:
            h.send(("u", p, 1))
        rt.flush()
        assert [e.data[1] for e in got] == [1, 2, 3, 3]

    def test_batch_window_reset(self):
        rt = build(self.APP.format(window="#window.lengthBatch(2)"))
        got = q_callback(rt, "q")
        h = rt.get_input_handler("T")
        for row in [("u1", "a", 1), ("u1", "b", 1), ("u1", "b", 1), ("u1", "b", 1)]:
            h.send(row)
            rt.flush()
        currents = [e.data[1] for e in got if not e.is_expired]
        # batch 1: a,b → 1,2 ; batch 2 (after reset): b,b → 1,1
        assert currents == [1, 2, 1, 1]


class TestDistinctPairEviction:
    """Lifetime-unique pairs past capacity must not corrupt counts: the
    capacity monitor compacts the append-only pair table, evicting dead
    (count==0) pairs (reference behavior: HashMap entries are removed
    naturally on processRemove)."""

    def test_counts_stay_correct_past_lifetime_capacity(self):
        import warnings as _warnings

        rt = SiddhiManager().create_siddhi_app_runtime(
            "@app:playback\n"
            "define stream S (k long);\n"
            "@info(name='q') from S#window.time(1 sec) "
            "select distinctCount(k) as dc insert into Out;",
            batch_size=8, group_capacity=64)
        rt.start()
        got = []
        rt.add_query_callback("q", lambda ts, i, r: got.extend(
            e.data[0] for e in i or []))
        h = rt.get_input_handler("S")
        # 64 waves x 8 fresh values = 512 lifetime-unique >> capacity 64;
        # waves are 2 s apart so at most one wave is ever live
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")  # post-compaction warn = failure
            for wave in range(64):
                base_ts = 2_000 * wave
                for j in range(8):
                    h.send((wave * 8 + j,), timestamp=base_ts + j)
                rt.flush()
        # final wave: running distinct within the window is 1..8
        assert got[-8:] == [1, 2, 3, 4, 5, 6, 7, 8]


class TestUnionSetForwarding:
    """Forwarded raw unionSet: downstream consumers get the LONG set-size
    projection; sizeOfSet reads it exactly (docs/PARITY.md divergence
    note; reference UnionSetAttributeAggregatorExecutor.java:71)."""

    def test_insert_into_table_then_size_of_set(self):
        from siddhi_tpu import SiddhiManager
        app = ("define stream S (sym string);\n"
               "define table T (s long);\n"
               "@info(name='fw') from S select unionSet(sym) as s "
               "insert into T;")
        rt = SiddhiManager().create_siddhi_app_runtime(app, batch_size=8)
        rt.start()
        h = rt.get_input_handler("S")
        for x in ("a", "b", "a", "c"):
            h.send((x,))
            rt.flush()
        rows = rt.query("from T select sizeOfSet(s) as n")
        assert [r.data for r in rows] == [(1,), (2,), (2,), (3,)]
        # callback boundary still materializes the REAL set
        got = []
        rt.add_query_callback(
            "fw", lambda ts, i, r: got.extend(e.data for e in i or []))
        h.send(("d",))
        rt.flush()
        assert got[-1][0] == {"a", "b", "c", "d"}
