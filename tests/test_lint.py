"""siddhi_tpu.analysis tests: golden corpus (one seeded defect per rule,
exact rule IDs), suppression, the SIDDHI_LINT startup gate, the jaxpr hazard
pass, the CLI, REST validate, and the zero-false-positive sweep over every
app string that builds in this tree."""

import json
import pathlib
import re
import threading
import urllib.request

import pytest

from siddhi_tpu import compiler
from siddhi_tpu.analysis import Severity, analyze
from siddhi_tpu.analysis.rules import RULES
from siddhi_tpu.core.manager import SiddhiManager
from siddhi_tpu.errors import SiddhiAppCreationError, SiddhiParserError
from siddhi_tpu.lint import lint_text, main as lint_main

pytestmark = pytest.mark.smoke

REPO = pathlib.Path(__file__).resolve().parent.parent
CORPUS = REPO / "tests" / "lint_corpus"

#: filename prefix → (expected rule, expected severity)
CORPUS_EXPECTATIONS = {
    "sl101": ("SL101", Severity.ERROR),
    "sl102": ("SL102", Severity.WARN),
    "sl103": ("SL103", Severity.ERROR),
    "sl104": ("SL104", Severity.ERROR),
    "sl105": ("SL105", Severity.INFO),
    "sl106": ("SL106", Severity.WARN),
    "sl107": ("SL107", Severity.WARN),
    "sl108": ("SL108", Severity.WARN),
    "sl109": ("SL109", Severity.ERROR),
    "sl110": ("SL110", Severity.WARN),
    "sl111": ("SL111", Severity.ERROR),
    "sl112": ("SL112", Severity.ERROR),
    "sl113": ("SL113", Severity.WARN),
    "sl114": ("SL114", Severity.INFO),
    "sl116": ("SL116", Severity.ERROR),
    "sl117": ("SL117", Severity.WARN),
    "sl118": ("SL118", Severity.INFO),
    "sl119": ("SL119", Severity.INFO),
    "sl120": ("SL120", Severity.INFO),
    "sl501": ("SL501", Severity.ERROR),
    "sl502": ("SL502", Severity.ERROR),
    "sl503": ("SL503", Severity.WARN),
    "sl505": ("SL505", Severity.INFO),
    "sl506": ("SL506", Severity.INFO),
    "sl601": ("SL601", Severity.ERROR),
    "sl602": ("SL602", Severity.WARN),
}


def _corpus_files():
    files = sorted(CORPUS.glob("*.siddhi"))
    assert len(files) == len(CORPUS_EXPECTATIONS)
    return files


class TestGoldenCorpus:
    @pytest.mark.parametrize("path", _corpus_files(),
                             ids=lambda p: p.stem)
    def test_corpus_app_flags_its_rule(self, path):
        rule_id, severity = CORPUS_EXPECTATIONS[path.stem.split("_")[0]]
        report = analyze(path.read_text())
        hits = [d for d in report.diagnostics if d.rule_id == rule_id]
        assert hits, (f"{path.name}: expected {rule_id}, got "
                      f"{[d.rule_id for d in report.diagnostics]}")
        assert all(d.severity is severity for d in hits)
        # the seeded defect is the ONLY rule of its severity class firing
        same_class = {d.rule_id for d in report.diagnostics
                      if d.severity is severity}
        assert same_class == {rule_id}

    def test_corpus_diagnostics_carry_locations(self):
        for path in _corpus_files():
            report = analyze(path.read_text())
            assert all(d.loc is not None for d in report.diagnostics), \
                path.name

    def test_rule_catalog_ids_are_unique(self):
        ids = [r[0] for r in RULES]
        assert len(ids) == len(set(ids))
        assert set(CORPUS_EXPECTATIONS.values()) <= {
            (rid, sev) for rid, sev, _fn, _d in RULES}


class TestSuppression:
    def test_element_level_suppression(self):
        app = """
        define stream S (price double);
        @suppress.lint('SL110')
        from S[1 > 2] select price insert into Out;
        """
        assert "SL110" not in analyze(app).rule_counts()

    def test_app_level_suppression(self):
        app = """
        @app:name('Sup')
        @suppress.lint('SL102')
        define stream Orphan (x int);
        define stream S (price double);
        from S select price insert into Out;
        """
        assert "SL102" not in analyze(app).rule_counts()

    def test_argless_suppression_silences_element(self):
        app = """
        define stream S (price double);
        @suppress.lint
        from S[1 > 2] select price insert into Out;
        """
        assert analyze(app).rule_counts() == {}

    def test_unsuppressed_still_fires(self):
        app = """
        define stream S (price double);
        from S[1 > 2] select price insert into Out;
        """
        assert "SL110" in analyze(app).rule_counts()


class TestLintGate:
    BAD = (CORPUS / "sl109_shadowed_query.siddhi").read_text()
    GOOD = """
    @app:name('CleanApp')
    define stream S (price double);
    from S[price > 0.0] select price insert into Out;
    """

    def test_default_warn_mode_builds_and_attaches_report(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_LINT", raising=False)
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(self.BAD)
        assert rt.lint_report is not None
        assert rt.lint_report.has_errors
        m.shutdown()

    def test_error_mode_refuses_corpus_app(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_LINT", "error")
        m = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError, match="SL109"):
            m.create_siddhi_app_runtime(self.BAD)
        m.shutdown()

    def test_error_mode_accepts_clean_app(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_LINT", "error")
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(self.GOOD)
        assert not rt.lint_report.has_errors
        m.shutdown()

    def test_off_mode_skips_lint(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_LINT", "off")
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(self.BAD)
        assert rt.lint_report is None
        m.shutdown()

    def test_statistics_report_carries_lint_section(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_LINT", raising=False)
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(self.BAD)
        stats = rt.statistics_report()
        assert stats["lint"]["valid"] is False
        assert stats["lint"]["rules"].get("SL109") == 1
        m.shutdown()

    def test_manager_validate_returns_report_without_runtime(self):
        m = SiddhiManager()
        report = m.validate(self.BAD)
        assert "SL109" in report.rule_counts()
        assert not m.runtimes


class TestJaxprPass:
    def test_detects_cron_window_host_callback(self):
        # the one host callback a step can carry: #window.cron asks the
        # host's cron clock for its next fire time, under a lax.cond — so
        # the walk has to descend below the step's top-level equations
        app = """
        define stream S (symbol string, price double);
        @info(name='cron')
        from S#window.cron('*/5 * * * * ?')
        select symbol, price
        insert into Out;
        """
        report = analyze(app, jaxpr=True)
        hits = [d for d in report.diagnostics if d.rule_id == "SL201"]
        assert hits and hits[0].severity is Severity.WARN
        assert "host" in hits[0].message

    @pytest.mark.parametrize("app", [
        pytest.param("""
        define stream S (symbol string, price double);
        @info(name='grouped')
        from S#window.lengthBatch(16384)
        select symbol, avg(price) as ap
        group by symbol
        insert into Out;
        """, id="groupby_16384_lanes"),
        pytest.param("""
        define stream T (symbol string, price double);
        define stream Q (symbol string, bid double);
        @info(name='joined')
        from T#window.length(16384) as t join Q#window.length(16384) as q
          on t.symbol == q.symbol
        select t.symbol, t.price, q.bid
        insert into Out;
        """, id="equi_join_length_16384"),
    ])
    def test_wide_sort_steps_stay_on_the_device(self, app):
        # wide enough for stable_argsort_bounded's packed arm: the traced
        # step carries no callback and the cost model prices it on device
        report = analyze(app, jaxpr=True)
        assert "SL201" not in report.rule_counts()
        assert "SL204" not in report.rule_counts()
        queries = [e for e in report.cost["elements"]  # cost_for_plan's
                   if e["kind"] in ("query", "join")]
        assert queries
        assert all(e["dispatch"] == "device" for e in queries)

    @pytest.mark.parametrize("nest", ["cond", "scan", "jit"])
    def test_walk_descends_into_nested_jaxprs(self, nest):
        # a callback below the top-level equations, in each construct that
        # carries a sub-jaxpr in its params
        import jax
        import jax.numpy as jnp
        from siddhi_tpu.analysis.jaxpr_pass import _trace_hazards

        def host_clock(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((), jnp.int32), x)

        def step(x):
            if nest == "cond":
                return jax.lax.cond(x < 0, host_clock, lambda v: v, x)
            if nest == "scan":
                return jax.lax.scan(
                    lambda c, _: (host_clock(c), None), x, None, length=2)[0]
            return jax.jit(host_clock)(x)

        hazards = _trace_hazards(step, jnp.int32(1))
        assert hazards.callbacks, nest
        assert any("host callback" in v for v in hazards.fastpath_vetoes)

    def test_pass_refuses_a_jax_without_jaxpr_classes(self, monkeypatch):
        # a walk that cannot recognise a nested jaxpr would see nothing
        # below the top level and certify the rest unseen: refuse instead
        import importlib

        import jax.core
        import jax.extend.core
        from siddhi_tpu.analysis import jaxpr_pass
        try:
            with monkeypatch.context() as m:
                for mod in (jax.extend.core, jax.core):
                    m.delattr(mod, "ClosedJaxpr", raising=False)
                with pytest.raises(ImportError, match="ClosedJaxpr"):
                    importlib.reload(jaxpr_pass)
        finally:
            importlib.reload(jaxpr_pass)

    def test_clean_passthrough_has_no_callback_warning(self):
        app = """
        define stream S (price double);
        from S[price > 0.0] select price insert into Out;
        """
        report = analyze(app, jaxpr=True)
        assert "SL201" not in report.rule_counts()


class TestParseErrorLocations:
    def test_parse_error_carries_line_column_snippet(self):
        with pytest.raises(SiddhiParserError) as ei:
            compiler.parse("define stream S (price double);\nfrom ???")
        e = ei.value
        assert e.line == 2
        assert e.snippet and "^" in e.snippet
        assert f"at line {e.line}:" in str(e)

    def test_lint_text_wraps_parse_failure_as_sl000(self):
        report = lint_text("define stream S (price double")
        assert report.rule_counts() == {"SL000": 1}
        d = report.diagnostics[0]
        assert d.severity is Severity.ERROR and d.loc is not None

    def test_lint_and_parser_share_location_format(self):
        report = analyze((CORPUS / "sl110_dead_query.siddhi").read_text())
        d = report.diagnostics[0]
        assert re.search(r" at line \d+:\d+$", d.format())


class TestCli:
    def test_cli_flags_whole_corpus(self, capsys):
        rc = lint_main(["--scan", "--json", str(CORPUS)])
        out = json.loads(capsys.readouterr().out)
        assert len(out) == len(CORPUS_EXPECTATIONS)
        for path, result in out.items():
            rule_id, _sev = CORPUS_EXPECTATIONS[
                pathlib.Path(path).stem.split("_")[0]]
            assert rule_id in result["counts"], path
        assert rc in (0, 1)  # 1 iff some corpus rule is an ERROR

    def test_cli_exit_codes(self, tmp_path, capsys):
        clean = tmp_path / "clean.siddhi"
        clean.write_text("define stream S (price double);\n"
                         "from S[price > 0.0] select price insert into O;\n")
        assert lint_main([str(clean)]) == 0
        bad = tmp_path / "bad.siddhi"
        bad.write_text("define stream S (price double);\n"
                       "from Ghost select * insert into O;\n")
        assert lint_main([str(bad)]) == 1
        broken = tmp_path / "broken.siddhi"
        broken.write_text("define stream S (")
        assert lint_main([str(broken)]) == 2
        capsys.readouterr()


class TestRestValidate:
    @pytest.fixture()
    def server(self):
        from siddhi_tpu.service import SiddhiService
        svc = SiddhiService(token="tkn")
        httpd = svc.make_server(port=0)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        yield f"http://127.0.0.1:{httpd.server_address[1]}"
        httpd.shutdown()

    def _post(self, url, body, token=None):
        req = urllib.request.Request(url, data=body.encode(), method="POST")
        if token:
            req.add_header("Authorization", f"Bearer {token}")
        try:
            with urllib.request.urlopen(req) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:  # pragma: no cover - auth path
            return e.code, json.loads(e.read())

    def test_validate_endpoint_reports_without_deploying(self, server):
        bad = (CORPUS / "sl101_undefined_stream.siddhi").read_text()
        code, body = self._post(f"{server}/siddhi-apps/validate", bad,
                                token="tkn")
        assert code == 200
        assert body["valid"] is False
        assert "SL101" in body["counts"]

    def test_validate_requires_auth(self, server):
        import urllib.error
        req = urllib.request.Request(
            f"{server}/siddhi-apps/validate",
            data=b"define stream S (x int);", method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 401

    def test_validate_handles_parse_failure_in_band(self, server):
        code, body = self._post(f"{server}/siddhi-apps/validate",
                                "define stream S (", token="tkn")
        assert code == 200
        assert body["counts"] == {"SL000": 1}


TRIPLE = re.compile(r'("""|\'\'\')(.*?)\1', re.DOTALL)


def _in_tree_app_strings():
    """Every triple-quoted SiddhiQL-looking string under tests/ + samples/."""
    for root in ("tests", "samples"):
        for p in (REPO / root).rglob("*.py"):
            for m in TRIPLE.finditer(p.read_text()):
                s = m.group(2)
                if "define stream" in s and (
                        "insert into" in s or "select" in s):
                    yield str(p), s


def test_zero_false_positives_on_in_tree_apps(monkeypatch):
    """Every app string in this tree that parses AND builds must lint with
    zero ERROR findings — the linter may not reject working apps."""
    monkeypatch.setenv("SIDDHI_LINT", "off")
    m = SiddhiManager()
    built = 0
    failures = []
    for src, text in _in_tree_app_strings():
        try:
            app = compiler.parse(text)
        except Exception:
            continue  # deliberately-invalid fixtures are out of scope
        try:
            rt = m.create_siddhi_app_runtime(app)
        except Exception:
            continue
        built += 1
        report = analyze(app)
        if report.has_errors:
            failures.append((src, [d.format() for d in report.errors]))
        rt.shutdown()
        m.runtimes.pop(app.name, None)
    assert built >= 25, f"sweep found too few buildable apps ({built})"
    assert not failures, failures
