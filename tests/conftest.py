"""Test configuration: run everything on a virtual 8-device CPU mesh so sharding
tests work without TPU hardware (the chip is reached only through
`chip_smoke.py` and `benchmarks/run.py`, never from pytest).

Also hosts the multi-process test harness: `worker_fleet` launches real OS
worker processes (fresh interpreters — jax.distributed and service workers
both need env-configured startup, not a fork of this mesh-configured
process), with deterministic port allocation, output capture, the shared
"MULTIHOST UNSUPPORTED" named-skip contract, and guaranteed teardown.
"""

import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from siddhi_tpu.util.platform import force_cpu_platform

force_cpu_platform(8)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "smoke: fast representative tier — `pytest -m smoke` finishes in "
        "~2-3 min on one core (full suite needs tens of minutes there)")
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`) — bounded bench runs "
        "and other multi-minute cases")


class WorkerFleet:
    """Launcher/janitor for multi-process integration tests: spawns worker
    subprocesses with the repo on PYTHONPATH, hands out free localhost
    ports, waits on HTTP bring-up, and guarantees every child is reaped on
    teardown no matter how the test exits.

    Two spawn shapes:
      * `spawn_script(source, args)` — a fresh interpreter running inline
        worker source (the jax.distributed bring-up pattern: platform env
        must be set BEFORE the interpreter imports jax, so forking the
        mesh-configured test process is not an option);
      * `spawn_service(port)` — a `python -m siddhi_tpu.service <port>`
        worker host on the CPU backend (the multi-host shard tier's
        worker shape).
    """

    #: sentinel a distributed worker prints when the backend cannot run
    #: cross-process computations at all (capability limit, not a defect)
    UNSUPPORTED_SENTINEL = "MULTIHOST UNSUPPORTED"
    UNSUPPORTED_SKIP = (
        "jax CPU backend cannot execute cross-process computations "
        "(XLA INVALID_ARGUMENT: \"Multiprocess computations aren't "
        "implemented on the CPU backend\") — this capability test "
        "needs a real multi-host TPU/GPU backend")

    def __init__(self, tmp_path) -> None:
        self.tmp_path = tmp_path
        self.procs: list = []

    # ------------------------------------------------------------- plumbing

    @staticmethod
    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def _env(self, overrides=None) -> dict:
        env = dict(os.environ)
        # workers own their platform choice (set it in overrides or in the
        # worker source itself, BEFORE jax imports)
        env.pop("JAX_PLATFORMS", None)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        if overrides:
            env.update(overrides)
        return env

    # --------------------------------------------------------------- spawns

    def spawn(self, argv, *, env=None, name=None) -> subprocess.Popen:
        p = subprocess.Popen(
            argv, cwd=str(self.tmp_path), env=self._env(env),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        p.fleet_name = name or os.path.basename(str(argv[1]))
        self.procs.append(p)
        return p

    def spawn_script(self, source: str, args=(), *, env=None,
                     name="worker.py") -> subprocess.Popen:
        path = self.tmp_path / name
        path.write_text(source)
        return self.spawn([sys.executable, str(path), *map(str, args)],
                          env=env, name=name)

    def spawn_service(self, port: int, *, env=None) -> subprocess.Popen:
        overrides = {"JAX_PLATFORMS": "cpu"}
        if env:
            overrides.update(env)
        return self.spawn(
            [sys.executable, "-m", "siddhi_tpu.service", str(port)],
            env=overrides, name=f"service:{port}")

    # ----------------------------------------------------------------- waits

    @staticmethod
    def wait_http_ready(port: int, timeout: float = 60.0,
                        path: str = "/health") -> None:
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=2.0) as r:
                    if r.status == 200:
                        return
                    last = r.status
            except (urllib.error.URLError, OSError) as e:
                last = e
            time.sleep(0.05)
        pytest.fail(f"worker on port {port} never served {path} "
                    f"(last: {last})")

    def communicate_all(self, timeout: float = 420.0) -> list:
        """Wait for every spawned process; on any timeout, kill the whole
        fleet and fail. Returns the combined stdout/stderr per process in
        spawn order."""
        outs = []
        for p in self.procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.shutdown()
                pytest.fail(f"worker {p.fleet_name} timed out")
            outs.append(out)
        return outs

    def skip_if_unsupported(self, outs) -> None:
        """Turn the worker-side capability sentinel into a NAMED skip —
        the test stays real on TPU/GPU multi-host CI."""
        if any(self.UNSUPPORTED_SENTINEL in out for out in outs):
            pytest.skip(self.UNSUPPORTED_SKIP)

    # -------------------------------------------------------------- teardown

    def kill(self, proc) -> None:
        """SIGKILL one worker (the host-kill chaos fault — no goodbye)."""
        from siddhi_tpu.util.faults import kill_host
        kill_host(proc)

    def shutdown(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for p in self.procs:
            try:
                p.communicate(timeout=30)
            except Exception:  # noqa: BLE001 — best-effort reap
                pass


@pytest.fixture
def worker_fleet(tmp_path):
    fleet = WorkerFleet(tmp_path)
    try:
        yield fleet
    finally:
        fleet.shutdown()
