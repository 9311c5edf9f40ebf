"""Multi-host shard serving tier: kill-one-host failover drill.

The tentpole proof for parallel/front_tier.py — a router process forwards
SXF1 frames to two REAL worker processes (`python -m siddhi_tpu.service`),
one worker is SIGKILLed mid-traffic, and the drill must show:

  * exact conservation — sent == delivered + spool_replayed + diverted,
    zero silent loss, checked after drain();
  * per-key-ordered multiset parity vs a no-kill oracle (bit-identical:
    values are multiples of 0.25 with small sums, and per-event running
    aggregates are batch-boundary invariant);
  * the failover surfaces: Prometheus families, a shard_failover flight-
    recorder bundle, a doctor critical finding, /ready degradation;
  * zombie fencing — the killed host resurrected after takeover is
    refused at its stale epoch, with frames rejected-and-recounted, never
    double-applied.

The in-process tests below it cover the satellite seams deterministically
(stale-router 409 reroute, lost-ack dedupe, unowned-slot divert, spool
restart adoption) using threaded services instead of subprocesses.
"""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from siddhi_tpu import doctor
from siddhi_tpu.core.manager import SiddhiManager
from siddhi_tpu.parallel.front_tier import FrontTier, _http
from siddhi_tpu.service import SiddhiService
from siddhi_tpu.state.error_store import InMemoryErrorStore
from siddhi_tpu.telemetry.prometheus import (FRONT_TIER_ALWAYS_ON,
                                             validate_exposition)
from siddhi_tpu.util import faults

APP = """
@app:name('FailApp')
@app:shards(n='4', key='k')
define stream S (k string, v double);
@info(name='q1')
from S select k, sum(v) as total, count() as n group by k insert into Out;
"""

#: same computation, no shards annotation: ONE plain runtime is the oracle
ORACLE_APP = """
@app:name('FailOracle')
define stream S (k string, v double);
@info(name='q1')
from S select k, sum(v) as total, count() as n group by k insert into Out;
"""

N_KEYS = 17
ROWS_PER_FRAME = 32


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _frames(n_frames: int):
    """Deterministic traffic: keys K0..K16 cycling, v a multiple of 0.25
    (sums stay exactly representable in float32 AND float64 — parity can
    demand bit equality), timestamps strictly increasing."""
    out = []
    t = 0
    for f in range(n_frames):
        rows, tss = [], []
        for r in range(ROWS_PER_FRAME):
            i = f * ROWS_PER_FRAME + r
            rows.append((f"K{i % N_KEYS}", ((i % 7) + 1) * 0.25))
            t += 1
            tss.append(t)
        out.append((rows, tss))
    return out


def _oracle(frames):
    """{key: [(total, n), ...] in emission order} from one plain runtime
    fed the SAME frames (same batching, same timestamps)."""
    rt = SiddhiManager().create_siddhi_app_runtime(ORACLE_APP)
    got = []
    rt.add_callback("Out", lambda evs: got.extend(
        [list(e.data) for e in evs]))
    rt.start()
    h = rt.get_input_handler("S")
    for rows, tss in frames:
        h.send_batch(rows, timestamps=tss)
    rt.flush()
    rt.drain()
    rt.shutdown()
    by_key: dict = {}
    for k, total, n in got:
        by_key.setdefault(str(k), []).append((float(total), int(n)))
    return by_key


def _worker_outputs(front) -> dict:
    """{key: [(total, n), ...]} fetched per shard from its CURRENT owner
    (an adopted shard's full history was re-emitted during WAL replay)."""
    by_key: dict = {}
    for shard in range(front.n_shards):
        owner = front.shard_owner[shard]
        assert owner is not None, f"shard {shard} has no owner"
        url = front.hosts[owner].url
        status, body = _http(
            "GET", f"{url}/shard-host/outputs?app={front.name}"
            f"&shard={shard}", timeout=30.0)
        assert status == 200, (status, body)
        for _stream, _ts, data in body["outputs"].get(str(shard), []):
            k, total, n = data
            by_key.setdefault(str(k), []).append((float(total), int(n)))
    return by_key


# ========================================================================= #
# the chaos drill: real subprocess workers, SIGKILL one mid-traffic
# ========================================================================= #


def test_kill_one_host_shard_failover(worker_fleet, tmp_path):
    ports = [_free_port(), _free_port()]
    for p in ports:
        worker_fleet.spawn_service(p)
    for p in ports:
        worker_fleet.wait_http_ready(p)

    wal_dir = str(tmp_path / "wal")
    bundles = str(tmp_path / "bundles")
    front = FrontTier(
        APP, [f"http://127.0.0.1:{p}" for p in ports], wal_dir=wal_dir,
        heartbeat_interval_s=0.3, miss_threshold=3,
        max_retries=1, retry_initial_s=0.02, retry_max_s=0.05,
        capture=["Out"], bundle_dir=bundles,
        recorder_cooldown_s=0.0, recorder_min_interval_s=0.0,
        # the deploy POST waits for a cold worker to build the app: over the
        # default 5 s when the suite's other workers hold the cores (a dead
        # host refuses at once, so no step below waits this long)
        request_timeout_s=30.0)
    front.start()
    try:
        frames = _frames(30)
        h = front.get_input_handler("S")

        # phase 1: healthy traffic across both hosts
        for rows, tss in frames[:12]:
            h.send_batch(rows, timestamps=tss)
        assert front.ready()[0] == 200

        # host-kill fault: SIGKILL worker 1 BETWEEN frames (deterministic:
        # no request is in flight, so the ack-window race stays closed and
        # parity can demand bit equality)
        worker_fleet.kill(worker_fleet.procs[1])

        # phase 2: the FIRST post-kill frame spools (the dead owner's
        # sub-frames can't be delivered) and /ready must degrade — checked
        # immediately, well inside the >=0.9s detection window, so the
        # assertion stays deterministic even when chaos slows the senders
        rows, tss = frames[12]
        h.send_batch(rows, timestamps=tss)
        code, body = front.ready()
        assert code == 503 and not body["ready"], body
        assert front.spooled_frames_total > 0
        for rows, tss in frames[13:24]:
            h.send_batch(rows, timestamps=tss)

        # the detector + takeover run on the heartbeat thread
        deadline = time.monotonic() + 60
        while front.failovers_total < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert front.failovers_total == 1, "takeover never completed"
        assert all(o is not None for o in front.shard_owner)

        # phase 3: post-takeover traffic to the adopted shards
        for rows, tss in frames[24:]:
            h.send_batch(rows, timestamps=tss)
        front.drain(timeout_s=60)

        # exact conservation: zero silent loss
        cons = front.conservation_report()
        total_rows = 30 * ROWS_PER_FRAME
        assert cons["sent"] == total_rows, cons
        assert cons["spooled_pending"] == 0, cons
        assert cons["diverted"] == 0, cons
        assert cons["conserved"], cons
        assert cons["delivered"] + cons["spool_replayed"] == total_rows

        # per-key-ordered multiset parity vs the no-kill oracle,
        # bit-identical (running aggregates over 0.25-multiples)
        want = _oracle(frames)
        got = _worker_outputs(front)
        assert set(got) == set(want)
        for k in sorted(want):
            assert got[k] == want[k], (
                f"key {k}: got {got[k][:5]}... want {want[k][:5]}...")

        # --- failover surfaces ---------------------------------------- #
        stats = front.statistics_report()
        ft = stats["front_tier"]
        assert ft["failovers_total"] == 1
        assert ft["spooled_frames_total"] > 0
        dead_url = f"http://127.0.0.1:{ports[1]}"
        assert not ft["hosts"][dead_url]["up"]

        text = front.metrics_text()
        assert validate_exposition(text) == []
        for fam in FRONT_TIER_ALWAYS_ON:
            assert f"# TYPE {fam} " in text, fam
        assert 'siddhi_shard_failovers_total{app="FailApp"} 1' in text
        assert f'siddhi_router_host_up{{app="FailApp",host="{dead_url}"}}' \
            ' 0' in text

        rec = front.recorder.report()
        assert rec["triggers"].get("shard_failover", 0) >= 1
        assert rec["bundles_written"] >= 1

        # doctor: the detection bundle (frozen pre-takeover) must carry a
        # critical dead-owner finding naming slots and spool depth
        bdirs = sorted(os.path.join(bundles, d) for d in os.listdir(bundles)
                       if "shard_failover" in d)
        assert bdirs, os.listdir(bundles)
        findings = doctor.analyze(doctor.load_bundle(bdirs[0]))
        dead_findings = [f for f in findings
                        if f["severity"] == "critical"
                        and "dead shard owner" in f["title"]]
        assert dead_findings, findings
        assert "slots" in dead_findings[0]["evidence"]

        # recovered: the tier serves every shard again
        assert front.ready()[0] == 200

        # --- zombie fencing ------------------------------------------- #
        # resurrect the killed worker on the SAME port; its self-deploy at
        # the pre-takeover epoch must be refused against the durable meta
        worker_fleet.spawn_service(ports[1])
        worker_fleet.wait_http_ready(ports[1])
        moved = [i for i in range(front.n_shards)
                 if front.shard_epochs[i] > 0]
        assert moved
        status, body = _http(
            "POST", f"{dead_url}/shard-host/apps",
            body=json.dumps({"app": APP, "shards": moved,
                             "wal_dir": wal_dir, "epoch": 0}).encode())
        assert status == 200
        assert [f["shard"] for f in body["fenced"]] == moved, body
        assert body["deployed"] == [], body

        # a stale-epoch frame at the CURRENT owner: rejected and counted,
        # never applied
        sh = moved[0]
        owner_url = front.hosts[front.shard_owner[sh]].url
        rows, tss = frames[0]
        from siddhi_tpu.io import wire
        plan = front._plan("S")
        cols = {"k": np.array([r[0] for r in rows], dtype=object),
                "v": np.array([r[1] for r in rows])}
        frame = wire.encode_frame(plan, cols, len(rows),
                                  np.asarray(tss, dtype=np.int64))
        status, body = _http(
            "POST", f"{owner_url}/shard-host/frames/FailApp/S"
            f"?shard={sh}&epoch=0&seq=999999999999", body=frame,
            ctype="application/x-siddhi-frames")
        assert status == 409 and body["error"] == "stale-epoch", body
        status, body = _http(
            "GET", f"{owner_url}/shard-host/state?app=FailApp")
        assert body["stale_rejected"] >= 1

        # nothing double-applied: parity still holds bit-for-bit
        assert _worker_outputs(front) == want
        assert front.conservation_report()["conserved"]
    finally:
        front.shutdown()


# ========================================================================= #
# in-process seams (threaded services — deterministic, no subprocesses)
# ========================================================================= #


class _TierHarness:
    """N SiddhiService worker hosts on daemon threads + helpers."""

    def __init__(self, n_hosts: int) -> None:
        self.services = [SiddhiService() for _ in range(n_hosts)]
        self.ports = [_free_port() for _ in range(n_hosts)]
        self.servers = [svc.make_server(port)
                        for svc, port in zip(self.services, self.ports)]
        self.threads = [threading.Thread(target=s.serve_forever,
                                         daemon=True)
                        for s in self.servers]
        for t in self.threads:
            t.start()
        self.urls = [f"http://127.0.0.1:{p}" for p in self.ports]

    def close(self) -> None:
        for s in self.servers:
            s.shutdown()
            s.server_close()


@pytest.fixture
def tier2(tmp_path):
    h = _TierHarness(2)
    try:
        yield h
    finally:
        h.close()


@pytest.fixture
def tier1(tmp_path):
    h = _TierHarness(1)
    try:
        yield h
    finally:
        h.close()


@pytest.mark.smoke
def test_stale_router_is_rerouted_after_409(tier2, tmp_path):
    """A second router instance left on a pre-takeover view forwards to the
    OLD owner at the OLD epoch; the worker's 409 makes it refresh from the
    durable meta and re-dispatch once — rows applied exactly once."""
    wal_dir = str(tmp_path / "wal")
    mk = dict(wal_dir=wal_dir, heartbeat_interval_s=60.0,
              capture=["Out"], max_retries=0)
    front1 = FrontTier(APP, tier2.urls, **mk)
    front1.start()
    front2 = FrontTier(APP, tier2.urls, **mk)  # stale view: never started
    try:
        # takeover with BOTH hosts alive (operator-driven drain shape):
        # host 1's shards move to host 0 at a new epoch; the fence
        # broadcast drops host 1's replicas
        moved = [i for i, o in enumerate(front1.shard_owner) if o == 1]
        res = front1.failover(1)
        assert sorted(res["adopted"]) == moved and not res["unowned"]

        # a key owned by a moved shard, per the SAME slot map front2 holds
        key = next(f"K{i}" for i in range(200)
                   if front2.router.shard_of(f"K{i}") in moved)
        h2 = front2.get_input_handler("S")
        h2.send_batch([(key, 0.25), (key, 0.5)], timestamps=[1, 2])

        assert front2.stale_epoch_rejections >= 1
        assert front2.reroutes >= 1
        assert front2.epoch == front1.epoch  # refreshed from the meta
        cons = front2.conservation_report()
        assert cons["conserved"] and cons["delivered"] == 2, cons

        # applied exactly once, at the NEW owner
        sh = front2.router.shard_of(key)
        assert front2.shard_owner[sh] == 0
        got = _worker_outputs(front2)
        assert got[key] == [(0.25, 1), (0.75, 2)]
    finally:
        front1.shutdown()
        front2.shutdown()


@pytest.mark.smoke
def test_lost_ack_is_retried_and_deduped(tier1, tmp_path):
    """A forward whose worker processed the frame but whose ack never
    arrived is retried with the SAME seq; the worker's journaled seq mark
    rejects the duplicate, so rows apply exactly once."""
    front = FrontTier(APP, tier1.urls, wal_dir=str(tmp_path / "wal"),
                      heartbeat_interval_s=60.0, capture=["Out"],
                      max_retries=2, retry_initial_s=0.01,
                      retry_max_s=0.02)
    front.start()
    try:
        plan = faults.inject_after(front, "_post",
                                   faults.FaultPlan(nth=(1,), exc=OSError))
        h = front.get_input_handler("S")
        h.send_batch([("K1", 0.25), ("K1", 0.25)], timestamps=[1, 2])
        faults.restore(front, "_post")
        assert plan.fired == 1

        cons = front.conservation_report()
        assert cons["conserved"] and cons["delivered"] == 2, cons
        assert cons["deduped_frames"] == 1, cons

        # worker side agrees: one duplicate rejected, rows applied once
        sh_state = tier1.services[0].shard_host.state("FailApp")
        assert sh_state["duplicate_frames"] == 1, sh_state
        got = _worker_outputs(front)
        assert got["K1"] == [(0.25, 1), (0.5, 2)]
    finally:
        front.shutdown()


@pytest.mark.smoke
def test_unowned_slots_divert_to_error_store(tmp_path):
    """With NO surviving owner, frames divert to the replayable ErrorStore
    (kind="unowned") instead of blocking or vanishing, /ready degrades,
    the doctor names the condition, and metrics expose the depth."""
    store = InMemoryErrorStore()
    front = FrontTier(APP, [f"http://127.0.0.1:{_free_port()}"],
                      wal_dir=str(tmp_path / "wal"),
                      heartbeat_interval_s=60.0, error_store=store,
                      recorder_cooldown_s=0.0, recorder_min_interval_s=0.0)
    try:
        res = front.failover(0)  # the only host is dead: no survivors
        assert res["unowned"] == [0, 1, 2, 3]

        h = front.get_input_handler("S")
        h.send_batch([("K0", 0.25), ("K1", 0.5), ("K2", 0.75)],
                     timestamps=[1, 2, 3])

        cons = front.conservation_report()
        assert cons["diverted"] == 3 and cons["conserved"], cons
        entries = store.load("FailApp", kind="unowned")
        parked = sorted(ev for e in entries for ev in e.events)
        # replayable shape: (original_ts, row) pairs, decoded values
        assert parked == [(1, ("K0", 0.25)), (2, ("K1", 0.5)),
                          (3, ("K2", 0.75))]

        code, body = front.ready()
        assert code == 503 and body["unowned_slots"], body

        findings = doctor.analyze({"stats": front.statistics_report()})
        crit = [f for f in findings if f["severity"] == "critical"
                and "unowned" in f["title"]]
        assert crit, findings
        assert "slots" in crit[0]["evidence"]

        text = front.metrics_text()
        assert validate_exposition(text) == []
        assert 'siddhi_router_unowned_slots{app="FailApp"} 64' in text
    finally:
        front.shutdown()


@pytest.mark.smoke
def test_router_restart_adopts_pending_spool(tmp_path):
    """Spooled frames survive a router restart: the new incarnation reads
    the durable spool back, keeps conservation balanced, and starts its
    seq counter above every spooled seq (worker dedupe stays monotone)."""
    wal_dir = str(tmp_path / "wal")
    url = f"http://127.0.0.1:{_free_port()}"
    front = FrontTier(APP, [url], wal_dir=wal_dir,
                      heartbeat_interval_s=60.0, max_retries=0,
                      retry_initial_s=0.01, retry_max_s=0.01)
    front.hosts[0].up = False  # owner unreachable, NOT confirmed dead:
    h = front.get_input_handler("S")  # frames must spool, not divert
    h.send_batch([("K0", 0.25), ("K1", 0.5)], timestamps=[1, 2])
    cons = front.conservation_report()
    assert cons["spooled_pending"] == 2 and cons["conserved"], cons
    max_seq = max(front._seq)
    front.shutdown()

    front2 = FrontTier(APP, [url], wal_dir=wal_dir,
                       heartbeat_interval_s=60.0)
    try:
        cons2 = front2.conservation_report()
        assert cons2["spooled_pending"] == 2, cons2
        assert cons2["sent"] == 2 and cons2["conserved"], cons2
        assert max(front2._seq) >= max_seq
        assert front2.ready()[0] == 503  # backlog = not ready
    finally:
        front2.shutdown()
