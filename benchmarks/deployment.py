"""The system under test, built the way a user builds it, and the traps
around it (copies of chip_smoke.py's `_Deployment`, `_CompileLog` and
`_EngineLogTrap`: later PRs may change the smoke, not the yardstick).

    SiddhiManager -> create_siddhi_app_runtime(app text) -> columnar async
    callback on the output stream -> SiddhiService.make_server(port=0) on a
    real socket, served from a thread of this process.
"""

from __future__ import annotations

import logging
import threading

import loadgen
from registry import BenchmarkError


class CompileLog:
    """What jax compiled and what its persistent cache answered, from
    jax.monitoring events; a cache hit is timed too (it is the retrieval).
    One per process: jax offers no way to take a listener back."""

    def __init__(self) -> None:
        from jax import monitoring
        self.lock = threading.Lock()
        self.programs: list = []  # (fun_name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_requests = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self.lock:
                self.programs.append((kw.get("fun_name", "?"), seconds))

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
            return len(self.programs)

    def between(self, lo: int, hi=None) -> list:
        with self.lock:
            return list(self.programs[lo:hi])


class EngineLogTrap(logging.Handler):
    """Makes a run incorrect on anything the engine swallowed: every record
    at ERROR or above on the `siddhi_tpu` logger, and every WARNING that
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


def app_text(config: dict, sizes: dict) -> str:
    return "\n".join(config["app"]).format(app_name=config["app_name"],
                                           **sizes)


class Deployment:
    """One runtime of the configuration, served over a real socket, with
    `on_block` as the columnar async callback on its output stream."""

    def __init__(self, config: dict, sizes: dict, on_block) -> None:
        from siddhi_tpu import SiddhiManager
        from siddhi_tpu.service import SiddhiService
        self.config, self.sizes = config, sizes
        self.name = config["app_name"]
        self.mgr = SiddhiManager()
        self.rt = self.mgr.create_siddhi_app_runtime(
            app_text(config, sizes), batch_size=sizes["batch"],
            group_capacity=sizes["group_capacity"], async_callbacks=True)
        self.rt.add_callback(config["output_stream"], on_block, columnar=True)
        self.rt.start()
        self.server = SiddhiService(self.mgr).make_server(port=0)
        self.port = self.server.server_address[1]
        self._serve = threading.Thread(target=self.server.serve_forever,
                                       daemon=True, name="bench-http")
        self._serve.start()

    def stream_path(self, stream: str) -> str:
        return f"/siddhi-apps/{self.name}/streams/{stream}"

    def post(self, stream: str, body, timeout: float) -> int:
        """One frame from this process (warm-up and closing frames only).
        Returns the rows the server accepted."""
        status, accepted, _ = loadgen.post("127.0.0.1", self.port,
                                           self.stream_path(stream), body,
                                           timeout)
        if status != 200:
            raise BenchmarkError(f"POST to {stream} answered {status}")
        return accepted

    def warm(self, extra_buckets) -> dict:
        """Compile, or load from the persistent cache, the full-width step
        of every junction and the buckets the configuration names; nothing
        else of the ladder. A step that does not compile stops the run."""
        buckets = tuple(sorted(
            {j.batch_size for j in self.rt.junctions.values()}
            | set(extra_buckets)))
        warmed = self.rt.warmup(buckets)
        if warmed.failures:
            raise BenchmarkError("warm-up failed to compile: " + repr(
                {k: repr(v) for k, v in warmed.failures.items()}))
        return {"buckets": list(buckets), "compiles": dict(warmed)}

    def statistics(self) -> dict:
        return self.rt.statistics_report()

    def engine_compiles(self) -> int:
        return sum(self.rt.statistics.compiles.values())

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self._serve.join(timeout=10)
        self.rt.shutdown()
