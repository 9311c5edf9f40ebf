"""Stream-stream and stream-table join tests.

Mirrors the reference's join suite
(modules/siddhi-core/src/test/java/io/siddhi/core/query/join/JoinTestCase.java):
black-box through the public API.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager


def make(app, batch_size=8):
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(app, batch_size=batch_size)
    got = []
    rt.add_callback("OutStream", lambda evs: got.extend(e.data for e in evs))
    rt.start()
    return rt, got


class TestStreamStreamJoin:
    APP = ("define stream TickStream (symbol string, price float);\n"
           "define stream NewsStream (symbol string, headline string);\n"
           "from TickStream#window.length(10) join NewsStream#window.length(10) "
           "on TickStream.symbol == NewsStream.symbol "
           "select TickStream.symbol as symbol, price, headline "
           "insert into OutStream;")

    def test_inner_join_basic(self):
        rt, got = make(self.APP)
        rt.get_input_handler("TickStream").send(("IBM", 75.0))
        rt.flush()
        rt.get_input_handler("NewsStream").send(("IBM", "up"))
        rt.flush()
        assert got == [("IBM", 75.0, "up")]

    def test_inner_join_no_match(self):
        rt, got = make(self.APP)
        rt.get_input_handler("TickStream").send(("IBM", 75.0))
        rt.flush()
        rt.get_input_handler("NewsStream").send(("WSO2", "down"))
        rt.flush()
        assert got == []

    def test_join_both_directions(self):
        rt, got = make(self.APP)
        rt.get_input_handler("NewsStream").send(("IBM", "up"))
        rt.flush()
        rt.get_input_handler("TickStream").send(("IBM", 10.0))
        rt.flush()
        # tick arrival probes news window
        assert got == [("IBM", 10.0, "up")]

    def test_multiple_matches(self):
        rt, got = make(self.APP)
        n = rt.get_input_handler("NewsStream")
        n.send(("IBM", "a"))
        n.send(("IBM", "b"))
        rt.flush()
        rt.get_input_handler("TickStream").send(("IBM", 5.0))
        rt.flush()
        assert sorted(got) == [("IBM", 5.0, "a"), ("IBM", 5.0, "b")]

    def test_window_expiry_limits_matches(self):
        app = ("define stream A (symbol string, x int);\n"
               "define stream B (symbol string, y int);\n"
               "from A#window.length(1) join B#window.length(10) "
               "on A.symbol == B.symbol "
               "select A.symbol as symbol, x, y insert into OutStream;")
        rt, got = make(app)
        a = rt.get_input_handler("A")
        a.send(("IBM", 1))
        rt.flush()
        a.send(("IBM", 2))  # evicts x=1 from A's window
        rt.flush()
        rt.get_input_handler("B").send(("IBM", 9))
        rt.flush()
        assert got == [("IBM", 2, 9)]

    def test_left_outer_join(self):
        app = ("define stream A (symbol string, x int);\n"
               "define stream B (symbol string, y int);\n"
               "from A#window.length(5) left outer join B#window.length(5) "
               "on A.symbol == B.symbol "
               "select A.symbol as symbol, x, y insert into OutStream;")
        rt, got = make(app)
        rt.get_input_handler("A").send(("IBM", 1))
        rt.flush()
        # no B match: left outer emits with null右 (numeric null -> 0)
        assert got == [("IBM", 1, 0)]

    def test_unidirectional(self):
        app = ("define stream A (symbol string, x int);\n"
               "define stream B (symbol string, y int);\n"
               "from A#window.length(5) unidirectional join B#window.length(5) "
               "on A.symbol == B.symbol "
               "select A.symbol as symbol, x, y insert into OutStream;")
        rt, got = make(app)
        rt.get_input_handler("B").send(("IBM", 7))
        rt.flush()
        assert got == []  # B arrivals don't trigger
        rt.get_input_handler("A").send(("IBM", 1))
        rt.flush()
        assert got == [("IBM", 1, 7)]

    def test_non_equi_cross_join(self):
        app = ("define stream A (x int);\n"
               "define stream B (y int);\n"
               "from A#window.length(5) join B#window.length(5) on A.x < B.y "
               "select x, y insert into OutStream;")
        rt, got = make(app)
        b = rt.get_input_handler("B")
        b.send((5,))
        b.send((1,))
        rt.flush()
        rt.get_input_handler("A").send((3,))
        rt.flush()
        assert got == [(3, 5)]

    def test_join_with_aggregation(self):
        app = ("define stream A (symbol string, x int);\n"
               "define stream B (symbol string, y int);\n"
               "from A#window.length(10) join B#window.length(10) "
               "on A.symbol == B.symbol "
               "select A.symbol as symbol, sum(y) as total group by symbol "
               "insert into OutStream;")
        rt, got = make(app)
        b = rt.get_input_handler("B")
        b.send(("IBM", 10))
        b.send(("IBM", 20))
        rt.flush()
        rt.get_input_handler("A").send(("IBM", 1))
        rt.flush()
        # one arrival matching two B rows -> running sum emits per pair
        assert got[-1] == ("IBM", 30)


class TestStreamTableJoin:
    APP = ("define stream S (symbol string, qty int);\n"
           "define table Prices (symbol string, price float);\n"
           "from S join Prices on S.symbol == Prices.symbol "
           "select S.symbol as symbol, qty, price insert into OutStream;")

    def test_table_join(self):
        rt, got = make(self.APP)
        rt.tables["Prices"].insert_rows([("IBM", 75.0), ("WSO2", 57.0)])
        s = rt.get_input_handler("S")
        s.send(("IBM", 5))
        s.send(("ORCL", 3))
        rt.flush()
        assert got == [("IBM", 5, 75.0)]

    def test_table_join_updated_contents(self):
        rt, got = make(self.APP)
        rt.tables["Prices"].insert_rows([("IBM", 75.0)])
        rt.get_input_handler("S").send(("IBM", 1))
        rt.flush()
        rt.tables["Prices"].insert_rows([("ORCL", 10.0)])
        rt.get_input_handler("S").send(("ORCL", 2))
        rt.flush()
        assert got == [("IBM", 1, 75.0), ("ORCL", 2, 10.0)]


class TestHighFanoutPairs:
    """Regression: pair-block compaction must not truncate below the old
    k_max-per-probe bound at small batch sizes (review finding: a 4*B cap
    with B=4 dropped 24 of 40 matched pairs)."""

    def test_all_pairs_survive_small_batches(self):
        app = ("define stream L (k int, v int);\n"
               "define stream R (k int, v int);\n"
               "from L#window.length(16) join R#window.length(16) "
               "on L.k == R.k "
               "select L.v as lv, R.v as rv insert into OutStream;")
        rt, got = make(app, batch_size=4)
        lh = rt.get_input_handler("L")
        rh = rt.get_input_handler("R")
        # 10 build rows with the same key
        for i in range(10):
            rh.send((7, i))
        rt.flush()
        # 4 probe events, each matches all 10 build rows -> 40 pairs
        for j in range(4):
            lh.send((7, 100 + j))
        rt.flush()
        assert len(got) == 40
        assert sorted({p[0] for p in got}) == [100, 101, 102, 103]
        assert sorted({p[1] for p in got}) == list(range(10))


# ------------------------------------------------- the pair compaction alone


def _block(case: str, B: int, K: int, cap: int):
    """(cand i32[B,K], ok bool[B,K]) of one named shape of candidate block.
    Candidates are distinct and non-zero so that a zero can only be an
    invalid lane's."""
    rng = np.random.default_rng([29, B, K, cap])
    cand = (1 + rng.permutation(B * K)).astype(np.int32).reshape(B, K)
    ok = np.zeros((B, K), bool)
    if case == "empty":
        pass
    elif case == "one_a_probe":  # join_100k's regime: any one column each
        ok[np.arange(B), rng.integers(0, K, B)] = rng.random(B) < 0.9
    elif case == "sparse_random":
        ok = rng.random((B, K)) < 0.2
    elif case == "every_lane":
        ok[:] = True
    elif case == "one_probe_has_all":
        ok[B // 2] = True
    elif case in ("just_under_cap", "at_cap", "over_cap_inside_a_run"):
        # whole rows of K until the cap is near, then a run of K - 1 that
        # ends one short of it, on it, or straddles it
        full, rest = divmod(cap, K)
        assert full + 2 < B and K > 2 and rest == 0
        ok[:full - 1] = True
        tail = {"just_under_cap": K - 1, "at_cap": K,
                "over_cap_inside_a_run": K}[case]
        ok[full, K - tail:] = True  # a probe with none lies between
        if case == "over_cap_inside_a_run":
            ok[full - 1, 1:3] = True  # so the cap falls inside row `full`
            ok[full + 1] = True  # and a whole probe beyond it
    else:
        raise AssertionError(case)
    return cand, ok


_COMPACTIONS = [  # (case, B, K, pair_cap)
    ("empty", 64, 16, 256), ("one_a_probe", 512, 16, 2048),
    ("sparse_random", 256, 16, 1024), ("sparse_random", 300, 5, 700),
    ("every_lane", 64, 16, 256), ("every_lane", 64, 16, 1000),
    ("one_probe_has_all", 64, 16, 256), ("one_probe_has_all", 64, 16, 8),
    ("just_under_cap", 64, 16, 256), ("at_cap", 64, 16, 256),
    ("over_cap_inside_a_run", 64, 16, 256),
    ("sparse_random", 512, 1, 64), ("every_lane", 512, 1, 100),
    ("empty", 128, 1, 32)]


@pytest.mark.parametrize("case,B,K,cap", _COMPACTIONS, ids=[
    f"{c}-{b}x{k}-cap{p}" for c, b, k, p in _COMPACTIONS])
def test_compact_pairs_equals_flatnonzero(case, B, K, cap):
    """`compact_pairs` alone against numpy: the survivors are the first
    `pair_cap` valid candidates in probe-major order, oldest first within a
    probe; lanes past them read zero; what the call site counts as dropped
    is what did not come out."""
    import jax.numpy as jnp

    from siddhi_tpu.ops.join import compact_pairs
    cand, ok = _block(case, B, K, cap)
    lane, row, pv = (np.asarray(x) for x in compact_pairs(
        jnp.asarray(cand.reshape(-1)), jnp.asarray(ok.reshape(-1)), K, cap))
    assert lane.shape == row.shape == pv.shape == (cap,)
    assert lane.dtype == row.dtype == np.int32 and pv.dtype == bool
    keep = np.flatnonzero(ok.reshape(-1))[:cap]
    n = keep.size
    assert pv.tolist() == [True] * n + [False] * (cap - n)
    assert lane[:n].tolist() == (keep // K).tolist()
    assert row[:n].tolist() == cand.reshape(-1)[keep].tolist()
    assert not lane[n:].any() and not row[n:].any()
    # join_runtime's `dropped` (less the walk's truncations)
    assert max(int(ok.sum()) - cap, 0) == int(ok.sum()) - int(pv.sum())


# ------------------------------- the compacting step, through the engine


_BIG = 4096  # x join_max_matches 16 = 65,536 candidate lanes, pair_cap 32,768


class TestCompactingStep:
    """Batches over 2,048 compact their candidate block before any per-pair
    gather (`join_runtime._make_step`): the same rows in the same order as
    the narrow batches above give."""

    def test_stream_table_join_sort_path(self):
        app = ("define stream S (k int, qty int);\n"
               "define table Prices (k int, price float);\n"
               "from S join Prices on S.k == Prices.k "
               "select S.k as k, qty, price insert into OutStream;")
        rt, got = make(app, batch_size=_BIG)
        rng = np.random.default_rng(2901)
        # every third key twice in the table, a quarter of the keys absent
        table = [(k, float(k)) for k in range(0, 600)] + \
            [(k, k + 0.5) for k in range(0, 600, 3)]
        rt.tables["Prices"].insert_rows(table)
        keys = rng.integers(0, 800, _BIG).tolist()
        rt.get_input_handler("S").send_batch(
            [(k, i) for i, k in enumerate(keys)])
        rt.flush()
        want = [(k, i, p) for i, k in enumerate(keys)
                for tk, p in table if tk == k]
        assert 2 * _BIG > len(want) > _BIG  # compacted, and under the cap
        assert got == want
        assert not rt.statistics_report()["overflow"]

    def test_left_outer_join(self):
        app = ("define stream L (k int, v int);\n"
               "define stream R (k int, v int);\n"
               "from L#window.length(3000) left outer join "
               "R#window.length(3000) on L.k == R.k "
               "select L.k as k, L.v as lv, R.v as rv "
               "insert into OutStream;")
        rt, got = make(app, batch_size=_BIG)
        rng = np.random.default_rng(2902)
        right = rng.integers(0, 3000, _BIG).tolist()
        left = rng.integers(0, 3000, _BIG).tolist()
        rt.get_input_handler("R").send_batch(
            [(k, 10_000 + i) for i, k in enumerate(right)])
        rt.flush()
        assert got == []  # a right arrival with no match emits nothing
        rt.get_input_handler("L").send_batch(
            [(k, i) for i, k in enumerate(left)])
        rt.flush()
        window = [(k, 10_000 + i) for i, k in enumerate(right)][-3000:]
        rows_of: dict = {}
        for k, v in window:  # oldest first
            rows_of.setdefault(k, []).append(v)
        pairs = [(k, i, rv) for i, k in enumerate(left)
                 for rv in rows_of.get(k, ())]
        alone = [(k, i) for i, k in enumerate(left) if k not in rows_of]
        assert len(pairs) > 2048 and len(alone) > 1000
        # the pair block first, then the unmatched probes' null frames
        assert got[:len(pairs)] == pairs
        assert [g[:2] for g in got[len(pairs):]] == alone
        assert not rt.statistics_report()["overflow"]

    def test_no_scatter_moves_candidate_lanes(self):
        """What PR 29 bought, kept from coming back: the pair compaction
        scatters one head word per probe, never the 16x candidate lanes
        (38 ns an update on a v5e: 80 of the step's 183 ms at join_100k's
        width). Read from the jaxpr of both probe directions."""
        import jax

        from siddhi_tpu.analysis.jaxpr_pass import _steps_of, _walk
        from siddhi_tpu.core import dtypes
        app = ("define stream L (k int, v int);\n"
               "define stream R (k int, v int);\n"
               "from L#window.length(3000) join R#window.length(3000) "
               "on L.k == R.k "
               "select L.k as k, L.v as lv, R.v as rv insert into OutStream;")
        rt, _ = make(app, batch_size=_BIG)
        (join,) = [q for q in rt.query_runtimes.values()
                   if hasattr(q, "_step_left")]
        pair_cap = max(dtypes.config.join_pair_cap_factor * _BIG, 32768)
        assert pair_cap < _BIG * join.k_max  # the step compacts
        steps = list(_steps_of(join))
        assert [tag for tag, _, _ in steps] == ["/left", "/right"]
        for tag, step, args in steps:
            scatters = []

            def visit(eqn):
                if eqn.primitive.name.startswith("scatter"):
                    operand, _, updates = (v.aval for v in eqn.invars[:3])
                    scatters.append((operand.shape, updates.shape))

            _walk(jax.make_jaxpr(step.__wrapped__)(*args).jaxpr, visit)
            assert scatters, tag  # the walk saw the step's body
            wide = [s for s in scatters if int(np.prod(s[1])) > _BIG]
            assert not wide, (tag, wide)
            # the one scatter into the pair block: a head word per probe
            assert [u for o, u in scatters if o[0] == pair_cap] \
                == [(_BIG,)], (tag, scatters)
