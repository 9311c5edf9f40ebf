"""chip_smoke.py at toy size on the CPU, and the loud-failure repairs it
leans on: the smoke's body is the same code the chip runs (exactness,
conservation, the superstep phase, the log trap); its command line refuses
anything but a TPU; AsyncDecoder.drain() raises instead of spinning;
importing the package starts no backend; the compile cache goes where the
environment says, or to one fixed place in the checkout."""

import json
import logging
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import chip_smoke
from siddhi_tpu.errors import SiddhiAppRuntimeError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY = chip_smoke.Sizes(
    batch=256, keys=64, group_capacity=128, window=100, workers=2,
    superstep_k=4, warm_frames=1, frames_a=6, producers_b=3, frames_b=4,
    frames_c=8, phase_deadline_s=120, warmup_deadline_s=120)


def _run(argv, env=None, cwd=REPO):
    return subprocess.run([sys.executable, *argv], cwd=cwd, text=True,
                          capture_output=True, timeout=120,
                          env={**os.environ, **(env or {})})


class TestSmokeBody:
    def test_toy_run_is_exact_conserved_and_superstepped(self):
        rep = chip_smoke.run_smoke(TOY, "cpu", seed=3)
        assert rep["failures"] == [] and rep["ok"]
        assert rep["exact_match"] and rep["conserved"]
        assert rep["compiles_in_fed_windows"] == 0
        assert rep["phases"]["A"]["rows_out"] > 0
        assert rep["phases"]["B"]["checks"]["producer_order_kept"]
        assert rep["phases"]["C"]["supersteps_dispatched"] >= 2
        assert rep["phases"]["C"]["superstep_decline"] is None
        assert list(rep)[:2] == ["ok", "device"] and list(rep)[-1] == "claim"
        assert rep["claim"] is None
        # the last stdout line: the verdict and the device, no other key
        last = json.loads(chip_smoke.verdict_line(rep))
        assert list(last) == ["ok", "device"] and last["ok"] is True
        assert last["device"] == {
            "platform": "cpu", "kind": rep["device"]["kind"], "count": 8}
        assert json.loads(chip_smoke.verdict_line(
            {**rep, "ok": False}))["ok"] is False

    def test_declined_superstep_and_logged_error_fail_the_smoke(
            self, monkeypatch):
        from siddhi_tpu.core import superstep
        monkeypatch.setattr(superstep, "build_runner",
                            lambda pipeline, k: (None, "test decline"))
        # and an ERROR the engine logged and carried on from
        orig = chip_smoke._Deployment.pipeline_stats

        def noisy(self):
            logging.getLogger("siddhi_tpu").error("async readback failed")
            return orig(self)

        monkeypatch.setattr(chip_smoke._Deployment, "pipeline_stats", noisy)
        rep = chip_smoke.run_smoke(TOY, "cpu", seed=3)
        assert not rep["ok"]
        assert rep["phases"]["C"]["superstep_decline"] == "test decline"
        assert any("declined" in f for f in rep["failures"])  # the log trap
        assert any("supersteps did not engage" in f for f in rep["failures"])
        assert any("async readback failed" in f for f in rep["failures"])
        assert rep["exact_match"]  # the K=1 path it fell back to is right

    def test_pipeline_falling_back_to_the_ring_fails_the_smoke(
            self, monkeypatch):
        from siddhi_tpu.core import ingress

        def refuse(self, junction, workers):
            raise RuntimeError("no pipeline today")

        monkeypatch.setattr(ingress.IngressPipeline, "__init__", refuse)
        rep = chip_smoke.run_smoke(TOY, "cpu", seed=3)
        assert not rep["ok"]
        assert any("did not engage" in f for f in rep["failures"])
        assert any("falling back" in f for f in rep["failures"])

    def test_wrong_platform_is_refused_before_anything_is_built(self):
        with pytest.raises(chip_smoke.SmokeError, match="refusing"):
            chip_smoke.run_smoke(TOY, "tpu")

    def test_reference_is_per_event_running_aggregates(self):
        sym = np.array([0, 1, 0, 0, 1, 2, 0])
        price = np.array([1.0, 2.0, 800.0, 3.0, 4.0, 5.0, 6.0])
        ts, s, total, avg, n = chip_smoke.reference_rows(
            sym, price, np.arange(7), window=2)
        # 800.0 is filtered; windows: (1,2) (3,4) (5,6)
        assert ts.tolist() == [0, 1, 3, 4, 5, 6]
        assert total.tolist() == [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
        assert n.tolist() == [1, 1, 1, 1, 1, 1]
        ts, s, total, avg, n = chip_smoke.reference_rows(
            sym, price, np.arange(7), window=3)
        assert total.tolist() == [1.0, 2.0, 4.0, 4.0, 5.0, 6.0]
        assert n.tolist() == [1, 1, 2, 1, 1, 1]
        assert avg.tolist() == [1.0, 2.0, 2.0, 4.0, 5.0, 6.0]


class TestCommandLine:
    def test_main_exits_nonzero_on_cpu_and_prints_no_result(self):
        p = _run(["chip_smoke.py"], env={"JAX_PLATFORMS": "cpu"})
        assert p.returncode != 0
        assert "refusing" in p.stderr and "cpu" in p.stderr
        assert p.stdout.strip() == ""  # no JSON line to mistake for a pass

    def test_imports_start_no_backend(self):
        # a router or load generator may import the wire codec next to a
        # process that holds the chip: importing must not reach for one
        p = _run(["-c", "import siddhi_tpu, siddhi_tpu.service, "
                  "siddhi_tpu.io.wire, siddhi_tpu.parallel.front_tier"],
                 env={"JAX_PLATFORMS": "no_such_platform"})
        assert p.returncode == 0, p.stderr[-2000:]


class TestCompileCachePlacement:
    PROBE = ("import jax; {patch}from siddhi_tpu.util.platform import "
             "configure_compile_cache as c; print(c()); "
             "print(jax.config.jax_compilation_cache_dir)")
    #: stand in for the chip: the helper only asks jax which backend it has
    AS_TPU = "jax.default_backend = lambda: 'tpu'; "

    def _probe(self, patch="", env=None, cwd=REPO):
        base = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
        p = subprocess.run(
            [sys.executable, "-c", self.PROBE.format(patch=patch)], cwd=cwd,
            env={**base, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
                 **(env or {})},
            text=True, capture_output=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        return p.stdout.split()

    def test_environment_places_the_cache_and_code_sets_no_other(
            self, tmp_path):
        where = str(tmp_path / "cc")
        assert self._probe(self.AS_TPU, {
            "JAX_COMPILATION_CACHE_DIR": where}) == [where, where]

    def test_default_is_one_fixed_directory_in_the_checkout(self):
        want = os.path.join(REPO, ".jax_cache")
        assert self._probe(self.AS_TPU) == [want, want]
        assert self._probe(self.AS_TPU, cwd="/") == [want, want]

    def test_cpu_backend_stays_uncached_unless_asked(self, monkeypatch):
        # in-process is safe: on the CPU with the variable unset the helper
        # must touch nothing at all
        import jax

        from siddhi_tpu.util.platform import configure_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert configure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before


class TestDecoderDrainIsBounded:
    @staticmethod
    def _decoder():
        from siddhi_tpu.core.stream import AsyncDecoder
        return AsyncDecoder(maxsize=4)

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_dead_fetch_workers_make_drain_raise_naming_the_sequence(self):
        dec = self._decoder()
        for _ in range(dec.N_FETCH):
            dec._q.put(("not", "a", "work", "item"))  # dies on unpack
        for t in dec._threads[:dec.N_FETCH]:
            t.join(timeout=10)
            assert not t.is_alive()
        got = []

        class Receiver:
            @staticmethod
            def on_batch(host, now):
                got.append(host)

        dec.submit(Receiver, np.arange(4), 0)  # queued, never fetched
        done = threading.Event()
        err = []

        def drain():
            try:
                dec.drain(timeout=60)
            except SiddhiAppRuntimeError as e:
                err.append(str(e))
            done.set()

        threading.Thread(target=drain, daemon=True).start()
        assert done.wait(timeout=10), "drain() spun on a stranded sequence"
        assert got == [] and len(err) == 1
        assert "siddhi-fetch-0" in err[0] and "sequence 0 of 1" in err[0]
        with pytest.raises(SiddhiAppRuntimeError):
            dec.stop()  # still tears down; the error reaches the caller

    def test_stalled_delivery_trips_the_deadline_then_recovers(self):
        dec = self._decoder()
        gate = threading.Event()

        class Receiver:
            @staticmethod
            def on_batch(host, now):
                gate.wait(timeout=30)

        dec.submit(Receiver, np.arange(4), 0)
        dec.submit(Receiver, np.arange(4), 1)
        with pytest.raises(SiddhiAppRuntimeError, match="no progress"):
            dec.drain(timeout=0.5)
        gate.set()
        dec.stop()  # alive threads: a later drain completes
        assert dec._deliver_next == 2

    def test_failed_readback_is_routed_not_delivered(self, monkeypatch):
        from siddhi_tpu.core.stream import AsyncDecoder
        dec = self._decoder()
        routed, delivered = [], []

        class Ctx:
            controller_lock = threading.RLock()

        class Junction:
            ctx = Ctx()
            on_error_action = None

            @staticmethod
            def on_error(e, host):
                routed.append(repr(e))

        class Receiver:
            @staticmethod
            def on_batch(host, now):
                delivered.append(host)

        def boom(payload):
            raise RuntimeError("device lost")

        monkeypatch.setattr(AsyncDecoder, "_fetch", staticmethod(boom))
        dec.submit(Receiver, np.arange(4), 0, Junction)
        dec.stop()
        assert delivered == [] and routed == ["RuntimeError('device lost')"]

    def test_wire_pack_round_trips_a_batch(self):
        # the packing only switches on off the CPU; its math is testable here
        import jax

        from siddhi_tpu.core.event import EventBatch
        from siddhi_tpu.core.stream import _wire_pack, _wire_unpack
        ts = np.array([5_000_000_000, 5_000_000_007, 0, 5_000_000_003])
        batch = EventBatch.from_numpy(
            ts, {"v": np.arange(4, dtype=np.int32)}, 4)
        batch = batch.where_valid(np.array([True, True, False, True]))
        host = jax.device_get(jax.jit(_wire_pack)(batch))
        assert not bool(host[4])
        back = _wire_unpack(host)
        raw = jax.device_get(batch)
        keep = np.asarray(raw.valid)
        assert np.array_equal(back.valid, keep)
        assert np.array_equal(back.ts[keep], np.asarray(raw.ts)[keep])
        assert np.array_equal(back.types, np.asarray(raw.types))
        assert np.array_equal(back.cols["v"], np.asarray(raw.cols["v"]))


class TestWarmupHandsBackFailures:
    def test_failure_is_returned_and_logged_not_raised(self, monkeypatch):
        from siddhi_tpu import SiddhiManager
        rt = SiddhiManager().create_siddhi_app_runtime(
            "define stream S (v int);\n"
            "@info(name='good') from S[v > 0] select v insert into A;\n"
            "@info(name='bad') from S[v > 1] select v insert into B;\n",
            batch_size=64)

        def refuse(buckets=None):
            raise RuntimeError("compiler said no")

        monkeypatch.setattr(rt.query_runtimes["bad"], "warmup", refuse)
        res = rt.warmup((64,))
        assert res["good"] == 1 and "bad" not in res
        assert list(res.failures) == ["bad"]
        assert "compiler said no" in str(res.failures["bad"])


def test_native_cache_tag_covers_every_source(tmp_path):
    import shutil

    from siddhi_tpu import native
    src = os.path.join(REPO, "native")
    for name in native.SOURCES:
        shutil.copy(os.path.join(src, name), tmp_path / name)
    assert native._src_tag(str(tmp_path)) == native._src_tag(src)
    with open(tmp_path / "colring_core.h", "ab") as f:
        f.write(b"\n/* edited */\n")
    assert native._src_tag(str(tmp_path)) != native._src_tag(src)
