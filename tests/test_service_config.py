"""REST service + config manager + doc-gen tests (reference:
modules/siddhi-service/ deploy API, config/YAMLConfigManagerTestCase,
siddhi-doc-gen)."""

import json
import threading
import urllib.request

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.service import SiddhiService
from siddhi_tpu.util.config import InMemoryConfigManager, YAMLConfigManager
from siddhi_tpu.util.docgen import generate_markdown


pytestmark = pytest.mark.smoke

APP = """@app:name('svc')
define stream S (symbol string, price float);
define table T (symbol string, price float);
from S insert into T;
"""


@pytest.fixture()
def server():
    svc = SiddhiService()
    httpd = svc.make_server(port=0)  # ephemeral port
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}", svc
    httpd.shutdown()


def _req(url, method="GET", body=None):
    data = body.encode() if isinstance(body, str) else body
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req) as resp:
        return resp.status, json.loads(resp.read())


class TestService:
    def test_deploy_send_query_undeploy(self, server):
        base, _svc = server
        code, out = _req(f"{base}/siddhi-apps", "POST", APP)
        assert code == 201 and out["app"] == "svc"

        code, out = _req(f"{base}/siddhi-apps")
        assert out["apps"] == ["svc"]

        code, out = _req(f"{base}/siddhi-apps/svc/streams/S", "POST",
                         json.dumps({"events": [["IBM", 75.0], ["WSO2", 57.0]]}))
        assert out["accepted"] == 2

        code, out = _req(f"{base}/siddhi-apps/svc/query", "POST",
                         json.dumps({"query": "from T select symbol, price"}))
        assert sorted(r[0] for r in out["records"]) == ["IBM", "WSO2"]

        code, out = _req(f"{base}/siddhi-apps/svc", "DELETE")
        assert out["undeployed"] is True
        code, out = _req(f"{base}/siddhi-apps")
        assert out["apps"] == []

    def test_duplicate_deploy_rejected(self, server):
        base, _svc = server
        _req(f"{base}/siddhi-apps", "POST", APP)
        try:
            _req(f"{base}/siddhi-apps", "POST", APP)
            raised = False
        except urllib.error.HTTPError as e:
            raised = True
            assert e.code == 400
        assert raised
        _req(f"{base}/siddhi-apps/svc", "DELETE")

    def test_bad_json_body_returns_400(self, server):
        base, _svc = server
        _req(f"{base}/siddhi-apps", "POST", APP)
        try:
            _req(f"{base}/siddhi-apps/svc/streams/S", "POST", "not json")
            raised = False
        except urllib.error.HTTPError as e:
            raised = True
            assert e.code == 400
        assert raised
        _req(f"{base}/siddhi-apps/svc", "DELETE")

    def test_bad_app_returns_400(self, server):
        base, _svc = server
        try:
            _req(f"{base}/siddhi-apps", "POST", "definitely not siddhiql ;;;")
            raised = False
        except urllib.error.HTTPError as e:
            raised = True
            assert e.code == 400
        assert raised

    def test_persist_and_recover_endpoints(self, server, tmp_path):
        from siddhi_tpu.state.persistence import FileSystemPersistenceStore
        base, svc = server
        svc.manager.set_persistence_store(
            FileSystemPersistenceStore(str(tmp_path)))
        _req(f"{base}/siddhi-apps", "POST", APP)
        _req(f"{base}/siddhi-apps/svc/streams/S", "POST",
             json.dumps({"events": [["IBM", 75.0]]}))
        status, out = _req(f"{base}/siddhi-apps/svc/persist", "POST", "")
        assert status == 200 and out["revision"].endswith("_svc")
        status, out = _req(f"{base}/siddhi-apps/svc/recover", "POST", "")
        assert status == 200
        assert out == {"revision": out["revision"], "wal_replayed": 0}
        assert out["revision"].endswith("_svc")

    def test_persist_without_store_returns_400(self, server):
        import urllib.error
        base, svc = server
        _req(f"{base}/siddhi-apps", "POST",
             "@app:name('nostore')\ndefine stream S (v long);\n"
             "from S select v insert into Out;")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(f"{base}/siddhi-apps/nostore/persist", "POST", "")
        assert ei.value.code == 400

    def test_script_functions_rejected_by_default(self, server):
        base, _svc = server
        app = ("@app:name('scripted')\n"
               "define function sq[python] return int { return x * x };\n"
               "define stream S (x int);\n"
               "from S select sq(x) as y insert into Out;\n")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(f"{base}/siddhi-apps", "POST", app)
        assert ei.value.code == 400
        assert "script" in json.loads(ei.value.read())["error"]

    def test_allow_scripts_opt_in(self):
        svc = SiddhiService(allow_scripts=True)
        app = ("@app:name('scripted2')\n"
               "define function sq[python] return int { return x * x };\n"
               "define stream S (x int);\n"
               "from S select sq(x) as y insert into Out;\n")
        assert svc.deploy(app) == "scripted2"
        svc.undeploy("scripted2")


class TestServiceAuth:
    @pytest.fixture()
    def auth_server(self):
        svc = SiddhiService(token="s3cret")
        httpd = svc.make_server(port=0)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        yield f"http://127.0.0.1:{httpd.server_address[1]}"
        httpd.shutdown()

    def test_requests_without_token_rejected(self, auth_server):
        for method, path, body in [("GET", "/siddhi-apps", None),
                                   ("POST", "/siddhi-apps", APP),
                                   ("DELETE", "/siddhi-apps/svc", None)]:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _req(f"{auth_server}{path}", method, body)
            assert ei.value.code == 401

    def test_bearer_token_accepted(self, auth_server):
        req = urllib.request.Request(
            f"{auth_server}/siddhi-apps",
            headers={"Authorization": "Bearer s3cret"})
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200
            assert json.loads(resp.read()) == {"apps": []}


class TestConfigManager:
    YAML = """
extensions:
  - extension:
      name: inMemory
      namespace: source
      properties:
        topic: configuredTopic
properties:
  some.flag: "42"
"""

    def test_yaml_config_reader(self):
        cm = YAMLConfigManager(yaml_text=self.YAML)
        reader = cm.generate_config_reader("source", "inMemory")
        assert reader.read_config("topic") == "configuredTopic"
        assert reader.read_config("missing", "dflt") == "dflt"
        assert cm.extract_property("some.flag") == "42"

    def test_source_topic_from_config(self):
        from siddhi_tpu.io import InMemoryBroker
        InMemoryBroker.clear()
        manager = SiddhiManager()
        manager.set_config_manager(YAMLConfigManager(yaml_text=self.YAML))
        rt = manager.create_siddhi_app_runtime(
            "@source(type='inMemory', @map(type='passThrough'))\n"
            "define stream S (v long);\n"
            "from S select v insert into Out;")
        rt.start()
        got = []
        rt.add_callback("Out", lambda evs: got.extend(evs))
        # topic came from deployment config, not the annotation
        InMemoryBroker.publish("configuredTopic", (7,))
        rt.flush()
        assert [e.data[0] for e in got] == [7]
        InMemoryBroker.clear()

    def test_in_memory_config_manager(self):
        cm = InMemoryConfigManager({"sink.log.prefix": "XYZ"})
        assert cm.generate_config_reader("sink", "log").read_config("prefix") == "XYZ"


class TestDocGen:
    def test_markdown_covers_registered_extensions(self):
        md = generate_markdown()
        # registry keys are case-insensitive (stored lowercased)
        for needle in ("## Windows", "`lengthbatch`", "`cron`",
                       "## Aggregators", "`distinctcount`",
                       "## Sources", "`inmemory`", "## Sink distribution strategies"):
            assert needle in md


def test_the_listen_backlog_holds_a_connect_from_every_client():
    """Thirty-two clients connect while the accept loop is held up (here:
    not yet running). socketserver's backlog of 5 let the kernel drop the
    seventh SYN, and that client stood for a second at least: one producer
    of a benchmark cell out for a while, its events' stamps behind for
    good."""
    import socket
    httpd = SiddhiService().make_server(port=0)
    held = []
    try:
        for _ in range(32):
            held.append(socket.create_connection(httpd.server_address,
                                                 timeout=1.0))
    finally:
        for s in held:
            s.close()
        httpd.server_close()
    assert len(held) == 32
