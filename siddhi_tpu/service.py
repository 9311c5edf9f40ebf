"""REST deployment microservice.

Reference: modules/siddhi-service/ — an MSF4J/Swagger service exposing deploy/
undeploy/list of SiddhiQL apps (SiddhiApiServiceImpl.java:24). Here: a
stdlib ThreadingHTTPServer over one SiddhiManager.

Endpoints (JSON):
  POST   /siddhi-apps                 body = SiddhiQL text  → deploy + start
  GET    /siddhi-apps                 → list of app names
  DELETE /siddhi-apps/<name>          → shutdown + undeploy
  POST   /siddhi-apps/<name>/streams/<stream>  body = {"events": [[...], ...]}
  POST   /siddhi-apps/<name>/query    body = {"query": "from T select ..."}
  POST   /siddhi-apps/<name>/persist  → {"revision": "..."}
  POST   /siddhi-apps/<name>/recover  → {"revision": ..., "wal_replayed": n}
  POST   /siddhi-apps/<name>/upgrade[?force=true]
                                      body = SiddhiQL text of the NEW app
                                      version (same @app:name) → blue-green
                                      hot-swap (core/upgrade.py): state
                                      migrates, WAL tail replays, sources/
                                      routing cut over atomically; any
                                      pre-commit failure rolls back to v1
  POST   /siddhi-apps/<name>/replay   body = {"app"?: SiddhiQL, "wal_dir"?:
                                      path, "speed"?: float} → deterministic
                                      replay of recorded WAL segments
                                      against a candidate app (defaults:
                                      the deployed app over its own journal)
  GET    /siddhi-apps/<name>/errors?kind=&stream=
                                      → stored error entries (metadata)
  POST   /siddhi-apps/<name>/errors/replay
                                      body = {"kind"?, "stream"?, "ids"?}
                                      → re-send matching entries into their
                                      original streams, original timestamps
  GET    /siddhi-apps/<name>/statistics
  POST   /siddhi-apps/<name>/diagnostics
                                      → force a flight-recorder diagnostic
                                        bundle now (telemetry/recorder.py;
                                        bypasses the trigger rate limits);
                                        {"bundle": path, "recorder": {...}}
  GET    /slo                         → 200 when no declared objective is
                                        breached; 503 with per-app burn
                                        detail otherwise (same lock-free
                                        contract as /ready)
  GET    /health                      → 200 always while the process serves
  GET    /ready                       → 200 when every app is "running";
                                        503 with per-app detail otherwise
                                        (degraded = breaker open, or
                                        recovering) — lock-free, so a
                                        wedged deploy can't flap probes
  GET    /metrics                     → Prometheus text exposition
                                        (docs/OBSERVABILITY.md)

Shard-host endpoints (parallel/front_tier.py — this service doubles as a
worker host of the multi-host shard serving tier; docs/SHARDING.md):
  GET    /shard-host/ping             → liveness for the front tier's
                                        failure detector (auth-exempt,
                                        like the other probes)
  GET    /shard-host/state?app=       → owned shards, epochs, last seqs
  GET    /shard-host/outputs?app=&shard=  → captured output rows (tests)
  POST   /shard-host/apps             body = {app, shards, wal_dir,
                                        shard_epochs, capture,
                                        runtime_kwargs} → build + start
                                        shard replicas (epoch fence-checked)
  POST   /shard-host/adopt            body = {app, shard, epoch, wal_dir,
                                        capture, runtime_kwargs} → take
                                        over a dead host's shard by WAL
                                        replay; returns last_seq
  POST   /shard-host/fence            body = {app, shard_epochs} → drop
                                        owned shards behind the committed
                                        epochs (zombie fencing)
  POST   /shard-host/drain            body = {app} → flush+drain replicas
  POST   /shard-host/frames/<app>/<stream>?shard=&epoch=&seq=
                                      body = raw SXF1 frames → deliver to
                                        the owned replica; 409 not-owner /
                                        stale-epoch (the sender re-routes)

Probe note: /health, /ready, and /metrics skip bearer-token auth by design —
orchestrator probes and scrapers carry no credentials; the bodies expose
only app names, health states, and metric aggregates, never data or query
text.

Usage:  python -m siddhi_tpu.service [port]

Concurrency note: requests serialize through one lock — the engine is a
single-controller runtime by design (SURVEY §7); the service is a deployment
surface, not a data-plane load balancer.

Security: **deploying an app is code execution** — SiddhiQL may contain
`define function f[python] { ... }` bodies that run in-process. The service
therefore (a) rejects script-function definitions unless constructed with
`allow_scripts=True`, and (b) requires a shared bearer token on every request
when constructed with `token=...`. Always set a token before binding to a
non-loopback host.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from .core.manager import SiddhiManager
from .errors import SiddhiError
from .util.locks import named_lock, note_blocking


class SiddhiService:
    def __init__(self, manager: SiddhiManager | None = None, *,
                 token: str | None = None,
                 allow_scripts: bool = False) -> None:
        self.manager = manager or SiddhiManager()
        self.lock = named_lock("service.registry")
        self.token = token
        self.allow_scripts = allow_scripts
        if self.manager.error_store is None:
            # the /errors endpoints need a store to read; the bounded
            # in-memory default makes @OnError(action='STORE') / dead-letter
            # capture work out of the box on a fresh service
            from .state.error_store import InMemoryErrorStore
            self.manager.set_error_store(InMemoryErrorStore())
        self._shard_host = None

    @property
    def shard_host(self):
        """Worker-side shard adoption hooks, built on first /shard-host/*
        request — a service that never joins a front tier pays nothing."""
        if self._shard_host is None:
            with self.lock:
                if self._shard_host is None:
                    from .parallel.front_tier import ShardHost
                    self._shard_host = ShardHost(self.manager)
        return self._shard_host

    # ------------------------------------------------------------- operations

    def deploy(self, siddhi_ql: str) -> str:
        with self.lock:
            from . import compiler
            text = (compiler.update_variables(siddhi_ql)
                    if "${" in siddhi_ql else siddhi_ql)
            app = compiler.parse(text)
            if app.function_definitions and not self.allow_scripts:
                names = ", ".join(sorted(app.function_definitions))
                raise SiddhiError(
                    "app defines script functions (" + names + ") which "
                    "execute arbitrary code; start the service with "
                    "allow_scripts=True to permit them")
            if app.name in self.manager.runtimes:
                # reference service rejects duplicate deployment
                raise SiddhiError(f"app {app.name!r} is already deployed")
            rt = self.manager.create_siddhi_app_runtime(app)
            rt.start()
            return rt.app.name

    def undeploy(self, name: str) -> bool:
        with self.lock:
            rt = self.manager.runtimes.pop(name, None)
            if rt is None:
                return False
            rt.shutdown()
            return True

    def list_apps(self) -> list[str]:
        with self.lock:
            return sorted(self.manager.runtimes)

    def send(self, app: str, stream: str, events: list) -> int:
        with self.lock:
            rt = self.manager.runtimes[app]
            handler = rt.get_input_handler(stream)
            # one batched staging call for the whole payload (the REST body
            # is already a batch) — the engine's fast public path
            handler.send_batch([tuple(row) for row in events])
            rt.flush()
            return len(events)

    def send_frames(self, app: str, stream: str, body: bytes) -> int:
        """Binary columnar ingestion (Content-Type:
        application/x-siddhi-frames, io/wire.py SXF1 framing). The service
        lock covers only the runtime lookup: frame decode and staging run
        lock-free so N client connections feed the ingress pipeline
        concurrently — the engine's own junction/controller locks protect
        delivery. No flush: the pipeline (or the columnar path's immediate
        delivery) owns batching."""
        with self.lock:
            rt = self.manager.runtimes[app]
            handler = rt.get_input_handler(stream)
        from .io import wire
        return wire.deliver_frames(handler, body)

    def query(self, app: str, text: str) -> list:
        with self.lock:
            rt = self.manager.runtimes[app]
            return [list(e.data) for e in rt.query(text)]

    def attach_query(self, app: str, query_text: str,
                     name: str | None = None) -> dict:
        """Splice one query into a RUNNING app (manager.attach_query:
        per-splice SL501 admission + one-retrace splice, siblings
        undisturbed). Returns the deploy summary incl. deploy_ms."""
        with self.lock:
            return self.manager.attach_query(app, query_text, name=name)

    def detach_query(self, app: str, query_name: str) -> dict:
        """Splice one query out of a RUNNING app; frees its budget and
        retries the pending-app queue (manager.detach_query)."""
        with self.lock:
            return self.manager.detach_query(app, query_name)

    def statistics(self, app: str) -> dict:
        with self.lock:
            return self.manager.runtimes[app].statistics_report()

    def persist(self, app: str) -> str:
        with self.lock:
            return self.manager.runtimes[app].persist()

    def recover(self, app: str) -> dict:
        """Restore the last revision + replay the app's WAL (crash
        recovery over the deployment surface)."""
        with self.lock:
            return self.manager.runtimes[app].recover()

    def _parse_guarded(self, siddhi_ql: str):
        """Parse SiddhiQL with the same script-function gate as deploy():
        an upgrade/replay body is code-execution surface too."""
        from . import compiler
        text = (compiler.update_variables(siddhi_ql)
                if "${" in siddhi_ql else siddhi_ql)
        app = compiler.parse(text)
        if app.function_definitions and not self.allow_scripts:
            names = ", ".join(sorted(app.function_definitions))
            raise SiddhiError(
                "app defines script functions (" + names + ") which "
                "execute arbitrary code; start the service with "
                "allow_scripts=True to permit them")
        return app

    def upgrade(self, name: str, siddhi_ql: str, *,
                force: bool = False) -> dict:
        """Blue-green hot-swap of deployed app `name` to the new version in
        the body (core/upgrade.py). Held under the service lock: the swap
        replaces the manager routing entry every other endpoint resolves."""
        with self.lock:
            app = self._parse_guarded(siddhi_ql)
            if app.name != name:
                raise SiddhiError(
                    f"body deploys {app.name!r} but the URL names {name!r}; "
                    "an upgrade must keep the app name")
            return self.manager.upgrade(app, force=force)

    def replay(self, name: str, *, siddhi_ql: str | None = None,
               wal_dir: str | None = None,
               speed: float | None = None) -> dict:
        """Deterministic WAL replay against a candidate app (defaults to the
        deployed app replaying its own journal)."""
        import os
        with self.lock:
            rt = self.manager.runtimes[name]
            app = (self._parse_guarded(siddhi_ql) if siddhi_ql
                   else rt.app)
            if wal_dir is None:
                if rt.wal is None:
                    raise SiddhiError(
                        f"app {name!r} has no WAL; pass wal_dir explicitly")
                wal_dir = os.path.dirname(rt.wal.dir)
            return self.manager.replay(app, wal_dir, app_name=name,
                                       speed=speed)

    def errors(self, name: str, *, stream: str | None = None,
               kind: str | None = None) -> list[dict]:
        """Stored error entries for one app (metadata only: row payloads may
        not be JSON-safe and can be large — replay acts on the stored
        originals server-side)."""
        with self.lock:
            rt = self.manager.runtimes[name]
            es = rt.ctx.error_store
            if es is None:
                return []
            return [{"id": e.id, "timestamp": e.timestamp,
                     "stream": e.stream_name, "kind": e.kind,
                     "events": len(e.events), "cause": e.cause}
                    for e in es.load(name, stream, kind)]

    def replay_errors(self, name: str, *, stream: str | None = None,
                      kind: str | None = None,
                      ids: list | None = None) -> dict:
        """Re-send matching stored entries into their original streams with
        their original timestamps; each entry is discarded only once all its
        rows were accepted (ErrorStore.replay)."""
        with self.lock:
            rt = self.manager.runtimes[name]
            es = rt.ctx.error_store
            if es is None:
                return {"replayed_entries": 0, "replayed_events": 0}
            entries = es.load(name, stream, kind)
            if ids:
                wanted = {int(i) for i in ids}
                entries = [e for e in entries if e.id in wanted]
            n_entries = n_events = 0
            for e in entries:
                es.replay(e, rt)
                n_entries += 1
                n_events += len(e.events)
            rt.flush()
            return {"replayed_entries": n_entries,
                    "replayed_events": n_events}

    def validate(self, siddhi_ql: str) -> dict:
        """Static lint WITHOUT deploying (no runtime is created, nothing
        starts): the CLI's report shape over HTTP. Parse failures come back
        as an SL000 diagnostic in the same shape, not an HTTP error."""
        from .lint import lint_text
        report = lint_text(siddhi_ql)
        return report.to_dict()

    def health(self) -> dict:
        """Liveness: no lock — the process answering IS the signal (a
        liveness probe must not hang behind a long deploy)."""
        return {"status": "up", "apps": len(self.manager.runtimes)}

    def readiness(self) -> tuple[int, dict]:
        """Readiness: (http_status, body). 200 only when every deployed app
        reports "running"; a breaker-open/degraded or recovering app answers
        503 so load balancers drain traffic while the engine sheds load.

        Lock-free like /health: a wedged deploy holding the service lock
        must not 503-flap probes — runtime.health() reads GIL-atomic
        snapshots, and iterating a point-in-time copy of the runtime table
        tolerates concurrent deploy/undeploy (an app mid-removal simply
        drops out of this probe)."""
        apps = {}
        for name, rt in list(self.manager.runtimes.items()):
            try:
                apps[name] = rt.health()
            except Exception:  # racing undeploy/shutdown
                apps[name] = {"state": "stopped", "breakers": {},
                              "queues": {}}
        ready = all(a["state"] == "running" for a in apps.values())
        return (200 if ready else 503), {"ready": ready, "apps": apps}

    def slo(self) -> tuple[int, dict]:
        """SLO probe: (http_status, body). 200 while no declared objective
        is breached (apps without @slo annotations count as compliant);
        503 lets alerting/load-balancing key off burn-rate breaches the
        same way /ready keys off breaker state. Lock-free like /ready."""
        apps = {}
        breaching = False
        for name, rt in list(self.manager.runtimes.items()):
            eng = getattr(rt, "slo_engine", None)
            if eng is None:
                continue
            try:
                rep = eng.report()
            except Exception:  # racing undeploy/shutdown
                continue
            apps[name] = rep
            breaching = breaching or rep.get("breaching", False)
        return (503 if breaching else 200), {"ok": not breaching,
                                             "apps": apps}

    def diagnostics(self, name: str, reason: str = "api") -> dict:
        """Force a diagnostic bundle for one app (bypasses the recorder's
        de-dup/rate-limit gates — an operator asking for evidence gets
        evidence)."""
        with self.lock:
            rt = self.manager.runtimes[name]
        return rt.diagnostics(reason=reason)

    def metrics_text(self) -> str:
        """Prometheus text exposition for every deployed app. Lock-free:
        a scrape must never queue behind a deploy or a device step."""
        from .telemetry import prometheus
        return prometheus.render_manager(self.manager)

    # ---------------------------------------------------------------- server

    def make_server(self, port: int = 9090,
                    host: str = "127.0.0.1") -> ThreadingHTTPServer:
        service = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _reply(self, code: int, payload) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _body(self):
                n = int(self.headers.get("Content-Length", 0))
                return self.rfile.read(n).decode()

            def _raw_body(self) -> bytes:
                n = int(self.headers.get("Content-Length", 0))
                return self.rfile.read(n)

            def _route(self):
                """(path_parts, query_dict) — the path may carry a query
                string (?force=true, ?kind=sink); parse_qs flattens each
                key to its first value."""
                from urllib.parse import parse_qs, urlsplit
                u = urlsplit(self.path)
                parts = u.path.strip("/").split("/")
                q = {k: v[0] for k, v in parse_qs(u.query).items()}
                return parts, q

            def _authorized(self) -> bool:
                if service.token is None:
                    return True
                import hmac
                got = self.headers.get("Authorization", "")
                want = f"Bearer {service.token}"
                if hmac.compare_digest(got.encode(), want.encode()):
                    return True
                self._reply(401, {"error": "missing or bad bearer token"})
                return False

            def do_GET(self):
                note_blocking("http.handle")
                parts, query = self._route()
                # probe endpoints skip auth (orchestrator probes carry no
                # credentials; bodies expose names + states only)
                if parts == ["health"]:
                    self._reply(200, service.health())
                    return
                if parts == ["ready"]:
                    code, body = service.readiness()
                    self._reply(code, body)
                    return
                if parts == ["slo"]:
                    # auth-exempt like /ready: burn rates and objective IDs,
                    # never data or query text
                    code, body = service.slo()
                    self._reply(code, body)
                    return
                if parts == ["metrics"]:
                    # auth-exempt like /health: scrapers carry no bearer
                    # token; the body exposes names + aggregates, not data
                    from .telemetry import prometheus
                    body = service.metrics_text().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     prometheus.CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if parts == ["shard-host", "ping"]:
                    # auth-exempt liveness for the front tier's failure
                    # detector (same contract as /health)
                    self._reply(200, service.shard_host.ping())
                    return
                if not self._authorized():
                    return
                try:
                    if parts == ["shard-host", "state"]:
                        self._reply(200, service.shard_host.state(
                            query.get("app", "")))
                    elif parts == ["shard-host", "outputs"]:
                        shard = query.get("shard")
                        self._reply(200, service.shard_host.outputs(
                            query.get("app", ""),
                            int(shard) if shard is not None else None))
                    elif parts == ["siddhi-apps"]:
                        self._reply(200, {"apps": service.list_apps()})
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "statistics"):
                        self._reply(200, service.statistics(parts[1]))
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "errors"):
                        self._reply(200, {"errors": service.errors(
                            parts[1], stream=query.get("stream"),
                            kind=query.get("kind"))})
                    else:
                        self._reply(404, {"error": "not found"})
                except KeyError:
                    self._reply(404, {"error": "unknown app"})

            def do_POST(self):
                note_blocking("http.handle")
                if not self._authorized():
                    return
                parts, query = self._route()
                try:
                    if (len(parts) == 4 and parts[0] == "shard-host"
                            and parts[1] == "frames"):
                        seq = query.get("seq")
                        code, body = service.shard_host.deliver(
                            parts[2], parts[3],
                            shard=int(query.get("shard", 0)),
                            epoch=int(query.get("epoch", 0)),
                            seq=int(seq) if seq is not None else None,
                            body=self._raw_body())
                        self._reply(code, body)
                    elif parts == ["shard-host", "apps"]:
                        data = json.loads(self._body())
                        self._reply(200, service.shard_host.deploy(
                            data["app"], data.get("shards", []),
                            data.get("wal_dir"),
                            epoch=int(data.get("epoch", 0)),
                            shard_epochs=data.get("shard_epochs"),
                            capture=data.get("capture", ()),
                            runtime_kwargs=data.get("runtime_kwargs")))
                    elif parts == ["shard-host", "adopt"]:
                        data = json.loads(self._body())
                        self._reply(200, service.shard_host.adopt(
                            data["app"], int(data["shard"]),
                            int(data["epoch"]), data["wal_dir"],
                            capture=data.get("capture", ()),
                            runtime_kwargs=data.get("runtime_kwargs")))
                    elif parts == ["shard-host", "fence"]:
                        data = json.loads(self._body())
                        self._reply(200, service.shard_host.fence(
                            data["app"], data.get("shard_epochs")))
                    elif parts == ["shard-host", "drain"]:
                        data = json.loads(self._body())
                        self._reply(200, service.shard_host.drain(
                            data["app"]))
                    elif parts == ["siddhi-apps"]:
                        name = service.deploy(self._body())
                        self._reply(201, {"app": name})
                    elif parts == ["siddhi-apps", "validate"]:
                        self._reply(200, service.validate(self._body()))
                    elif (len(parts) == 4 and parts[0] == "siddhi-apps"
                          and parts[2] == "streams"):
                        ctype = (self.headers.get("Content-Type") or "")
                        if ctype.split(";")[0].strip() == \
                                "application/x-siddhi-frames":
                            # zero-copy columnar path: raw SXF1 frames
                            n = service.send_frames(parts[1], parts[3],
                                                    self._raw_body())
                        else:
                            data = json.loads(self._body())
                            n = service.send(parts[1], parts[3],
                                             data.get("events", []))
                        self._reply(200, {"accepted": n})
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "query"):
                        data = json.loads(self._body())
                        rows = service.query(parts[1], data["query"])
                        self._reply(200, {"records": rows})
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "queries"):
                        # attach: JSON {"query": ..., "name": ...} or a
                        # raw SiddhiQL query body
                        body = self._body()
                        ctype = (self.headers.get("Content-Type") or "")
                        if ctype.split(";")[0].strip() == \
                                "application/json":
                            data = json.loads(body)
                            out = service.attach_query(
                                parts[1], data["query"],
                                name=data.get("name"))
                        else:
                            out = service.attach_query(parts[1], body)
                        self._reply(201, out)
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "persist"):
                        self._reply(200,
                                    {"revision": service.persist(parts[1])})
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "recover"):
                        self._reply(200, service.recover(parts[1]))
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "diagnostics"):
                        body = self._body()
                        data = json.loads(body) if body.strip() else {}
                        self._reply(200, service.diagnostics(
                            parts[1], reason=data.get("reason", "api")))
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "upgrade"):
                        force = query.get("force", "").lower() \
                            in ("1", "true", "yes")
                        self._reply(200, service.upgrade(
                            parts[1], self._body(), force=force))
                    elif (len(parts) == 3 and parts[0] == "siddhi-apps"
                          and parts[2] == "replay"):
                        body = self._body()
                        data = json.loads(body) if body.strip() else {}
                        speed = data.get("speed")
                        self._reply(200, service.replay(
                            parts[1], siddhi_ql=data.get("app"),
                            wal_dir=data.get("wal_dir"),
                            speed=float(speed) if speed is not None
                            else None))
                    elif (len(parts) == 4 and parts[0] == "siddhi-apps"
                          and parts[2] == "errors"
                          and parts[3] == "replay"):
                        body = self._body()
                        data = json.loads(body) if body.strip() else {}
                        self._reply(200, service.replay_errors(
                            parts[1], stream=data.get("stream"),
                            kind=data.get("kind"), ids=data.get("ids")))
                    else:
                        self._reply(404, {"error": "not found"})
                except KeyError as e:
                    self._reply(404, {"error": f"unknown: {e}"})
                except json.JSONDecodeError as e:
                    self._reply(400, {"error": f"bad JSON body: {e}"})
                except ValueError as e:  # bad SXF1 framing / column shape
                    self._reply(400, {"error": str(e)})
                except SiddhiError as e:
                    self._reply(400, {"error": str(e)})

            def do_DELETE(self):
                note_blocking("http.handle")
                if not self._authorized():
                    return
                parts, _query = self._route()
                try:
                    if len(parts) == 2 and parts[0] == "siddhi-apps":
                        ok = service.undeploy(parts[1])
                        self._reply(200 if ok else 404,
                                    {"undeployed": ok})
                    elif (len(parts) == 4 and parts[0] == "siddhi-apps"
                          and parts[2] == "queries"):
                        self._reply(200, service.detach_query(
                            parts[1], parts[3]))
                    else:
                        self._reply(404, {"error": "not found"})
                except KeyError as e:
                    self._reply(404, {"error": f"unknown: {e}"})
                except SiddhiError as e:
                    self._reply(400, {"error": str(e)})

        return _Server((host, port), Handler)


class _Server(ThreadingHTTPServer):
    #: the listen backlog. socketserver's 5 overflows as soon as the accept
    #: loop waits a few tens of ms for the interpreter (workers interning a
    #: frame hold it that long) while more than six clients reconnect — the
    #: server speaks HTTP/1.0, one connection a frame — and the kernel then
    #: drops the SYN: that client stands for 1 s at least (its SYN retry).
    #: 128 holds a connect from every client a deployment has
    request_queue_size = 128


def main(argv=None) -> None:
    import os
    import sys
    argv = argv if argv is not None else sys.argv[1:]
    from .telemetry.logs import configure_logging
    configure_logging()  # SIDDHI_LOG_FORMAT=json → structured one-liners
    from .util.platform import configure_compile_cache
    # starts the backend: a chip this process cannot have fails here, at
    # start-up, not at the first deploy; redeploys reuse compiled steps
    configure_compile_cache()
    allow_scripts = "--allow-scripts" in argv
    argv = [a for a in argv if a != "--allow-scripts"]
    port = int(argv[0]) if argv else 9090
    token = os.environ.get("SIDDHI_SERVICE_TOKEN") or None
    server = SiddhiService(token=token,
                           allow_scripts=allow_scripts).make_server(port)
    auth = "token auth" if token else "NO AUTH (loopback only!)"
    print(f"siddhi_tpu service on :{port} [{auth}]")
    server.serve_forever()


if __name__ == "__main__":
    main()
