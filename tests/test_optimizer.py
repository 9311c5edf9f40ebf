"""Optimizer mechanics: group formation, the decline taxonomy (loud, with
SL114 anchoring), statistics/Prometheus surfaces, compile-count sublinearity,
and the dark-sink re-light path. Output CORRECTNESS under fusion lives in
tests/test_optimizer_parity.py — this file tests the machinery around it."""

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.analysis import analyze_sharing
from siddhi_tpu.analysis.optimizer import (
    DECLINE_BREAKER,
    DECLINE_OBJECT,
    DECLINE_PARTITION,
)
from siddhi_tpu.core.shared import group_cap

pytestmark = pytest.mark.smoke

STREAM = "define stream S (symbol string, price double, volume long);\n"

FUSABLE = (STREAM +
           "@info(name='a') from S[price > 10.0] select symbol, price "
           "insert into OutA;\n"
           "@info(name='b') from S[price > 20.0] select symbol, volume "
           "insert into OutB;\n"
           "@info(name='c') from S select symbol insert into OutC;\n")


def _runtime(app, **kw):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, batch_size=8, **kw)
    return m, rt


def _feed(rt, n=12):
    h = rt.get_input_handler("S")
    for i in range(n):
        h.send(("IBM", 5.0 * i, i), timestamp=1000 + i)
    rt.flush()


# ----------------------------------------------------------------- opt-in


class TestOptIn:
    def test_off_by_default(self):
        m, rt = _runtime(FUSABLE)
        assert rt.optimizer_report is None or \
            not rt.optimizer_report.get("enabled")
        assert not getattr(rt, "shared_groups", ())
        m.shutdown()

    def test_app_annotation_opts_in(self):
        m, rt = _runtime("@app:optimize\n" + FUSABLE)
        assert rt.optimizer_report["queries_fused"] == 3
        m.shutdown()

    def test_env_var_opts_in(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_OPTIMIZE", "1")
        m, rt = _runtime(FUSABLE)
        assert rt.optimizer_report["queries_fused"] == 3
        m.shutdown()

    def test_kwarg_wins_over_annotation(self):
        m, rt = _runtime("@app:optimize\n" + FUSABLE, optimize=False)
        assert not getattr(rt, "shared_groups", ())
        m.shutdown()


# ------------------------------------------------------------- formation


class TestFormation:
    def test_groups_are_contiguous_runs(self):
        m, rt = _runtime(FUSABLE, optimize=True)
        groups = rt.shared_groups
        assert len(groups) == 1 and len(groups[0].members) == 3
        # delivery order preserved: members in source order
        assert [q.name for q in groups[0].members] == ["a", "b", "c"]
        m.shutdown()

    def test_group_cap_chunks_long_runs(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_OPTIMIZE_GROUP_CAP", "4")
        app = STREAM + "".join(
            f"@info(name='q{i}') from S[price > {i}.0] select symbol "
            f"insert into Out{i};\n" for i in range(10))
        m, rt = _runtime(app, optimize=True)
        sizes = sorted(len(g.members) for g in rt.shared_groups)
        assert sizes == [2, 4, 4]   # 10 split at cap=4, remainder kept
        assert sum(sizes) == 10
        m.shutdown()

    def test_single_query_never_grouped(self):
        app = STREAM + ("@info(name='only') from S select symbol "
                        "insert into Out;\n")
        m, rt = _runtime(app, optimize=True)
        assert not rt.shared_groups
        assert rt.optimizer_report["groups"] == 0
        m.shutdown()


# ---------------------------------------------------------------- declines


class TestDeclines:
    """The small-fix satellite: the optimizer declines LOUDLY — report +
    SL114 note — and never silently fuses different isolation semantics."""

    def _declined(self, app, qname):
        m, rt = _runtime(app, optimize=True)
        rep = rt.optimizer_report
        fused = {name for g in rt.shared_groups for name in
                 (q.name for q in g.members)}
        assert qname not in fused
        m.shutdown()
        return rep["declined"]

    def test_breaker_declines(self):
        app = (STREAM +
               "@info(name='plain') from S select symbol insert into O1;\n"
               "@info(name='frag') from S[price > 0.0] select symbol "
               "insert into O2;\n")
        app = app.replace("@info(name='frag')",
                          "@breaker(threshold='2')\n@info(name='frag')")
        declined = self._declined(app, "frag")
        assert declined.get("frag") == DECLINE_BREAKER

    def test_partition_declines(self):
        app = (STREAM +
               "@info(name='top') from S select symbol insert into O1;\n"
               "@info(name='top2') from S select volume insert into O2;\n"
               "partition with (symbol of S) begin "
               "@info(name='inner') from S select symbol, price "
               "insert into POut; end;\n")
        m, rt = _runtime(app, optimize=True)
        fused = {name for g in rt.shared_groups for name in
                 (q.name for q in g.members)}
        assert "inner" not in fused
        assert rt.optimizer_report["declined"].get(
            "inner") == DECLINE_PARTITION
        m.shutdown()

    def test_object_attribute_declines(self):
        app = ("define stream S (symbol string, payload object);\n"
               "@info(name='x') from S select symbol insert into O1;\n"
               "@info(name='y') from S[symbol == 'IBM'] select symbol "
               "insert into O2;\n")
        m, rt = _runtime(app, optimize=True)
        assert not rt.shared_groups
        reasons = set(rt.optimizer_report["declined"].values())
        assert reasons == {DECLINE_OBJECT}
        m.shutdown()

    def test_lone_query_declines_nothing(self):
        # a decline is only reported when sharing was actually forgone
        app = STREAM + ("@breaker(threshold='2')\n@info(name='solo') "
                        "from S select symbol insert into O;\n")
        rep = analyze_sharing(__import__(
            "siddhi_tpu").compiler.parse(app), enabled=True)
        assert rep.declined == {}


# ------------------------------------------------------------------ SL114


class TestSL114:
    def test_validate_reports_shareable_work(self):
        report = SiddhiManager().validate(FUSABLE)
        notes = [d for d in report.diagnostics if d.rule_id == "SL114"]
        assert notes, [d.format() for d in report.diagnostics]
        assert "3 queries" in notes[0].message

    def test_validate_reports_decline(self):
        app = (STREAM +
               "@info(name='plain') from S select symbol insert into O1;\n"
               "@breaker(threshold='2')\n"
               "@info(name='frag') from S select symbol insert into O2;\n")
        report = SiddhiManager().validate(app)
        msgs = [d.message for d in report.diagnostics if d.rule_id == "SL114"]
        assert any("declines" in m and "@breaker" in m for m in msgs), msgs

    def test_no_note_without_sharing(self):
        app = STREAM + "from S select symbol insert into Out;\n"
        report = SiddhiManager().validate(app)
        assert not [d for d in report.diagnostics if d.rule_id == "SL114"]


# ------------------------------------------------------- stats & prometheus


class TestReporting:
    def test_statistics_report_section(self):
        m, rt = _runtime(FUSABLE, optimize=True)
        rt.start()
        _feed(rt)
        sec = rt.statistics_report()["optimizer"]
        assert sec["enabled"] is True
        assert sec["groups"] == 1
        assert sec["queries_fused"] == 3
        assert sec["compiles_avoided"] >= 2   # one shape compiled so far
        assert list(sec["group_members"].values()) == [["a", "b", "c"]]
        m.shutdown()

    def test_statistics_report_when_off(self):
        m, rt = _runtime(FUSABLE)
        rt.start()
        assert rt.statistics_report()["optimizer"] == {"enabled": False}
        m.shutdown()

    def test_per_query_attribution_survives_fusion(self):
        m, rt = _runtime(FUSABLE, optimize=True)
        rt.statistics.set_level("detail")
        rt.start()
        _feed(rt)
        lat = rt.statistics_report()["query_latency_ms"]
        for q in ("a", "b", "c"):
            assert q in lat, lat
        m.shutdown()

    def test_prometheus_families(self):
        from siddhi_tpu.telemetry.prometheus import render_manager
        m, rt = _runtime(FUSABLE, optimize=True)
        rt.start()
        _feed(rt)
        body = render_manager(m)
        for fam in ("siddhi_optimizer_enabled", "siddhi_optimizer_groups",
                    "siddhi_optimizer_queries_fused",
                    "siddhi_optimizer_compiles_avoided_total"):
            assert fam in body, fam
        m.shutdown()


# --------------------------------------------------------- compile counts


class TestCompileCounts:
    @pytest.mark.parametrize("n_queries", [8, 64])
    def test_fused_compiles_once_per_group(self, n_queries):
        """Compiles scale with fused groups, not queries: one per group
        with the optimizer, one per query without it."""
        app = STREAM + "".join(
            f"@info(name='q{i}') from S[price > {i}.0] select symbol "
            f"insert into Out{i};\n" for i in range(n_queries))

        def compiles(optimize):
            m, rt = _runtime(app, optimize=optimize)
            rt.start()
            _feed(rt, n=8)   # one full batch, one shape
            rep = rt.statistics_report()
            m.shutdown()
            comp = rep["compiles"]
            return (sum(v for k, v in comp.items() if k.startswith("shared:")),
                    sum(v for k, v in comp.items() if k.startswith("q")),
                    rep["optimizer"])

        group_compiles, member_compiles, opt = compiles(True)
        assert opt["queries_fused"] == n_queries
        assert opt["groups"] == -(-n_queries // group_cap())
        assert group_compiles == opt["groups"]
        assert member_compiles == 0
        group_compiles, member_compiles, _ = compiles(False)
        assert group_compiles == 0
        assert member_compiles == n_queries


# ------------------------------------------------------------- dark sinks


class TestDarkSinkRelight:
    def test_late_callback_relights_member(self):
        """Dark members' outputs are dead-code-eliminated from the fused
        graph; attaching a callback mid-run must rebuild the jit (one
        retrace) and deliver from the next batch on."""
        m, rt = _runtime(FUSABLE, optimize=True)
        got_a, got_b = [], []
        rt.add_callback("OutA", lambda evs: got_a.extend(
            tuple(e.data) for e in evs))
        rt.start()
        _feed(rt, n=8)
        assert got_a and not got_b
        # OutB was dark through that batch; light it up now
        rt.add_callback("OutB", lambda evs: got_b.extend(
            tuple(e.data) for e in evs))
        h = rt.get_input_handler("S")
        for i in range(8):
            h.send(("IBM", 100.0 + i, i), timestamp=2000 + i)
        rt.flush()
        assert got_b, "re-lit member delivered nothing"
        assert len(got_b) == 8
        m.shutdown()
