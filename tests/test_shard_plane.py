"""Sharded execution plane (parallel/shard_plane.py + the ShardRouter).

The correctness contract under test: for a shard-eligible (key-local) app,
the MERGED output of N shards is bit-identical to the serial engine's as a
sorted multiset AND per partition key as an ordered sequence — routing
happens over ORIGINAL values before interning, per-key order is preserved
by the boolean-mask split, and a key's state never leaves its shard. All
values are multiples of 0.25 so per-key partial sums are exactly
representable: equality below is `==` on floats, not approx.

Plus the operational surface: the routing conservation identity, loud
SL601 refusal of global plans, skew-triggered rebalancing (epoch protocol,
WAL re-routing, refusal conditions), single-shard moves, kill/recover, and
the duck-typed manager/service integration (error store, upgrade guard,
Prometheus families).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.ingress import ShardRouter
from siddhi_tpu.errors import SiddhiAppCreationError
from siddhi_tpu.state.persistence import FileSystemPersistenceStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARDED_APP = """
@app:name('PlaneApp')
@app:shards(n='4', key='k')
define stream S (k string, v double);
@info(name='agg')
from S select k, sum(v) as total, count() as n group by k insert into Out;
"""
SERIAL_APP = SHARDED_APP.replace("@app:shards(n='4', key='k')\n", "") \
                        .replace("PlaneApp", "PlaneAppSerial")


def _rows(n: int, seed: int = 5, n_keys: int = 13):
    rng = np.random.default_rng(seed)
    ks = rng.integers(0, n_keys, n)
    vs = rng.integers(1, 400, n) * 0.25  # exact in float64
    return [(f"K{int(k)}", float(v)) for k, v in zip(ks, vs)]


def _by_key(rows_out):
    seqs: dict = {}
    for r in rows_out:
        seqs.setdefault(r[0], []).append(r)
    return seqs


def _run(mgr, app_text, rows, *, wal_dir=None, shutdown=True):
    rt = mgr.create_siddhi_app_runtime(app_text, wal_dir=wal_dir)
    out: list = []
    rt.add_callback("Out", lambda evs: out.extend(tuple(e.data)
                                                 for e in evs))
    rt.start()
    h = rt.get_input_handler("S")
    h.send_batch(rows, timestamps=list(range(1, len(rows) + 1)))
    rt.drain()
    if shutdown:
        rt.shutdown()
    return rt, out


class TestShardRouter:
    def test_scalar_vector_hash_agree(self):
        r = ShardRouter("k", 4, n_slots=64)
        cols = [
            np.array(["a", "b", "xyzzy", "", "a", "K7"], dtype=object),
            np.arange(-5, 11, dtype=np.int64),
            np.array([0.0, -1.5, 3.25, 1e9, -0.25]),
        ]
        for col in cols:
            vec = r.slots_of_column(col)
            scal = [r.slot_of(v) for v in col.tolist()]
            assert vec.tolist() == scal, col.dtype

    def test_dict_triple_slots_match_materialized(self):
        r = ShardRouter("k", 4, n_slots=64)
        values = ["K1", "K2", "K3"]
        idx = np.array([0, 2, 2, 1, 0, 1], dtype=np.int64)
        triple = ("dict", values, idx)
        vec = r.slots_of_column(triple)
        mat = r.slots_of_column(
            np.array([values[i] for i in idx], dtype=object))
        assert vec.tolist() == mat.tolist()

    def test_split_columns_conserves_and_keeps_keys_local(self):
        r = ShardRouter("k", 4, n_slots=64)
        n = 500
        rng = np.random.default_rng(3)
        cols = {"k": np.array([f"K{i % 17}" for i in range(n)],
                              dtype=object),
                "v": rng.normal(size=n)}
        ts = np.arange(n, dtype=np.int64)
        parts = r.split_columns(cols, ts, n)
        assert sum(cnt for _, _, cnt in parts.values()) == n
        owner: dict = {}
        for shard, (_, sub, cnt) in parts.items():
            assert len(sub["k"]) == cnt == len(sub["v"])
            for key in sub["k"].tolist():
                assert owner.setdefault(key, shard) == shard
        assert r.total_rows == n

    def test_split_rows_preserves_per_key_order(self):
        r = ShardRouter("k", 3, n_slots=16)
        rows = [(f"K{i % 5}", i) for i in range(60)]
        parts = r.split_rows(list(range(60)), rows, 0)
        for shard, (tss, srows) in parts.items():
            assert tss == sorted(tss)
            per_key: dict = {}
            for key, v in srows:
                per_key.setdefault(key, []).append(v)
            for key, vs in per_key.items():
                assert vs == sorted(vs), (shard, key)

    def test_assignment_validation(self):
        with pytest.raises(ValueError):
            ShardRouter("k", 2, n_slots=8,
                        assignment=[7] * 8)  # shard 7 out of range
        with pytest.raises(ValueError):
            ShardRouter("k", 2, n_slots=8, assignment=[0, 1])  # wrong len

    def test_propose_assignment_balances_hot_slots(self):
        r = ShardRouter("k", 2, n_slots=8)
        # all traffic lands on slots owned by shard 0 under the default
        # modulo assignment -> proposal must spread it
        r.slot_rows[0] = 1000
        r.slot_rows[2] = 1000
        r.routed[0] = 2000
        r.total_rows = 2000
        prop = r.propose_assignment()
        assert {int(prop[0]), int(prop[2])} == {0, 1}
        # cold slots keep their shard: no gratuitous moves
        assert all(int(prop[s]) == int(r.assignment[s])
                   for s in range(8) if s not in (0, 2))


class TestParity:
    pytestmark = pytest.mark.smoke

    @pytest.mark.parametrize("n", [1, 4, 8])
    def test_sharded_vs_serial_bit_identical(self, n):
        rows = _rows(2000)
        mgr = SiddhiManager()
        app = SHARDED_APP.replace("n='4'", f"n='{n}'")
        plane, got = _run(mgr, app, rows, shutdown=False)
        assert plane.n_shards == n
        assert plane.conservation_report()["conserved"] is True
        plane.shutdown()
        _, want = _run(SiddhiManager(), SERIAL_APP, rows)
        assert len(got) == len(want) == len(rows)
        assert sorted(got) == sorted(want)  # multiset, exact floats
        assert _by_key(got) == _by_key(want)  # per-key ORDERED sequences

    def test_parity_under_python_ring(self, tmp_path):
        """SIDDHI_NATIVE=0 forces the pure-Python ingress ring (decided at
        import time, hence the subprocess): same parity oracle."""
        script = tmp_path / "parity_py.py"
        script.write_text(
            "import sys; sys.path.insert(0, %r)\n" % REPO
            + "from siddhi_tpu.util.platform import force_cpu_platform\n"
            "force_cpu_platform(1)\n"
            "from tests.test_shard_plane import (SHARDED_APP, SERIAL_APP,"
            " _rows, _run, _by_key)\n"
            "from siddhi_tpu import SiddhiManager\n"
            "import siddhi_tpu.native as native_mod\n"
            "assert not native_mod.available()\n"
            "rows = _rows(800)\n"
            "_, got = _run(SiddhiManager(), SHARDED_APP, rows)\n"
            "_, want = _run(SiddhiManager(), SERIAL_APP, rows)\n"
            "assert sorted(got) == sorted(want)\n"
            "assert _by_key(got) == _by_key(want)\n"
            "print('PARITY-PY OK', len(got))\n")
        env = {**os.environ, "SIDDHI_NATIVE": "0", "JAX_PLATFORMS": "cpu"}
        p = subprocess.run([sys.executable, str(script)], env=env,
                           capture_output=True, text=True, timeout=420)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        assert "PARITY-PY OK 800" in p.stdout

    def test_conservation_identity(self):
        rows = _rows(1500, seed=9)
        mgr = SiddhiManager()
        plane, _ = _run(mgr, SHARDED_APP, rows, shutdown=False)
        rep = plane.conservation_report()
        plane.shutdown()
        assert rep["sent"] == len(rows)
        assert rep["conserved"] is True
        assert rep["sent"] == rep["delivered"] + rep["dropped"] \
            + rep["diverted"]
        per = rep["per_shard"]
        assert sum(s["delivered"] for s in per.values()) \
            == rep["delivered"]
        # every shard that was routed rows must account for them
        for s in per.values():
            assert s["routed"] == s["delivered"] + s["dropped"] \
                + s["diverted"]


class TestEligibility:
    def test_sl601_global_plan_refused_loudly(self):
        bad = """
        @app:name('BadPlane')
        @app:shards(n='4', key='k')
        define stream S (k string, v double);
        from S#window.length(100)
        select k, sum(v) as total group by k insert into Out;
        """
        with pytest.raises(SiddhiAppCreationError) as ei:
            SiddhiManager().create_siddhi_app_runtime(bad)
        assert "SL601" in str(ei.value)
        assert "shard-eligible" in str(ei.value)

    def test_stream_without_key_attribute_refused(self):
        app = """
        @app:name('NoKeyPlane')
        @app:shards(n='2', key='k')
        define stream S (k string, v double);
        define stream T (x long);
        @info(name='agg')
        from S select k, sum(v) as total group by k insert into Out;
        @info(name='echo') from T select x insert into TOut;
        """
        with pytest.raises(SiddhiAppCreationError) as ei:
            SiddhiManager().create_siddhi_app_runtime(app)
        assert "lacks the partition key" in str(ei.value)

    def test_env_override_and_shards_1(self):
        os.environ["SIDDHI_SHARDS"] = "2"
        try:
            plane = SiddhiManager().create_siddhi_app_runtime(SHARDED_APP)
        finally:
            os.environ.pop("SIDDHI_SHARDS", None)
        assert plane.n_shards == 2
        plane.shutdown()


class TestLifecycle:
    def test_rebalance_force_reroutes_and_preserves_state(self, tmp_path):
        rows = _rows(1200, seed=11)
        more = _rows(800, seed=12)
        mgr = SiddhiManager()
        plane, got = _run(mgr, SHARDED_APP, rows,
                          wal_dir=str(tmp_path), shutdown=False)
        res = plane.rebalance(force=True)
        assert res["rebalanced"] is True
        assert plane.epoch == 1
        assert res["replayed"] == len(rows)
        meta = json.load(open(tmp_path / "PlaneApp.shardmeta.json"))
        assert meta["epoch"] == 1 and meta["key"] == "k"
        # state continuity: running per-key aggregates keep counting
        h = plane.get_input_handler("S")
        h.send_batch(more, timestamps=list(
            range(len(rows) + 1, len(rows) + len(more) + 1)))
        plane.drain()
        plane.shutdown()
        _, want = _run(SiddhiManager(), SERIAL_APP, rows + more)
        assert sorted(got) == sorted(want)
        assert _by_key(got) == _by_key(want)

    def test_rebalance_noop_below_threshold(self, tmp_path):
        mgr = SiddhiManager()
        plane, _ = _run(mgr, SHARDED_APP, _rows(400),
                        wal_dir=str(tmp_path), shutdown=False)
        res = plane.rebalance(threshold=1e9)
        plane.shutdown()
        assert res["rebalanced"] is False
        assert "below" in res["reason"]
        assert plane.epoch == 0

    def test_rebalance_refused_without_wal(self):
        mgr = SiddhiManager()
        plane, _ = _run(mgr, SHARDED_APP, _rows(200), shutdown=False)
        with pytest.raises(SiddhiAppCreationError, match="needs a WAL"):
            plane.rebalance(force=True)
        plane.shutdown()

    def test_rebalance_refused_after_persist(self, tmp_path):
        mgr = SiddhiManager()
        mgr.set_persistence_store(
            FileSystemPersistenceStore(str(tmp_path / "snap")))
        plane, _ = _run(mgr, SHARDED_APP, _rows(200),
                        wal_dir=str(tmp_path / "wal"), shutdown=False)
        plane.persist()
        with pytest.raises(SiddhiAppCreationError, match="persist"):
            plane.rebalance(force=True)
        plane.shutdown()

    def test_move_shard_keeps_state_and_journal(self, tmp_path):
        rows = _rows(600, seed=21)
        more = _rows(400, seed=22)
        mgr = SiddhiManager()
        plane, got = _run(mgr, SHARDED_APP, rows,
                          wal_dir=str(tmp_path), shutdown=False)
        res = plane.move_shard(1)
        assert res == {"moved": 1, "epoch": 0}
        assert plane.shards[1].wal is not None  # journal handed over
        h = plane.get_input_handler("S")
        h.send_batch(more, timestamps=list(
            range(len(rows) + 1, len(rows) + len(more) + 1)))
        plane.drain()
        plane.shutdown()
        _, want = _run(SiddhiManager(), SERIAL_APP, rows + more)
        assert sorted(got) == sorted(want)
        assert _by_key(got) == _by_key(want)

    def test_kill_and_recover_shard(self, tmp_path):
        rows = _rows(600, seed=31)
        mgr = SiddhiManager()
        plane, got = _run(mgr, SHARDED_APP, rows,
                          wal_dir=str(tmp_path), shutdown=False)
        victim = 2
        plane.kill_shard(victim)
        assert plane.health()["state"] == "stopped"
        with pytest.raises(SiddhiAppCreationError, match="alive"):
            plane.recover_shard(0)
        rec = plane.recover_shard(victim)
        assert rec["wal_replayed"] > 0
        assert plane.health()["state"] in ("running", "recovering")
        plane.drain()
        plane.shutdown()
        # recovery REPLAYS the shard's journal: its rows re-emit, so the
        # multiset grows — but last-per-key (the running aggregate's final
        # value) must match the serial oracle exactly
        _, want = _run(SiddhiManager(), SERIAL_APP, rows)
        last = {r[0]: r for r in got}
        last_want = {r[0]: r for r in want}
        assert last == last_want

    def test_plane_recover_after_restart(self, tmp_path):
        rows = _rows(500, seed=41)
        mgr = SiddhiManager()
        plane, _ = _run(mgr, SHARDED_APP, rows, wal_dir=str(tmp_path))
        # fresh manager = simulated process restart on the same WAL layout
        mgr2 = SiddhiManager()
        plane2 = mgr2.create_siddhi_app_runtime(
            SHARDED_APP, wal_dir=str(tmp_path))
        out: list = []
        plane2.add_callback("Out",
                            lambda evs: out.extend(tuple(e.data)
                                                   for e in evs))
        plane2.start()
        rec = plane2.recover()
        assert rec["wal_replayed"] == len(rows)
        plane2.drain()
        plane2.shutdown()
        _, want = _run(SiddhiManager(), SERIAL_APP, rows)
        assert {r[0]: r for r in out} == {r[0]: r for r in want}


class TestIntegration:
    def test_statistics_and_skew_sections(self):
        mgr = SiddhiManager()
        plane, _ = _run(mgr, SHARDED_APP, _rows(300), shutdown=False)
        rep = plane.statistics_report()
        plane.shutdown()
        sp = rep["shard_plane"]
        assert sp["n_shards"] == 4 and sp["key"] == "k"
        assert sp["epoch"] == 0 and sp["rebalances"] == 0
        assert rep["conservation"]["conserved"] is True
        assert set(rep["shards"]) == {"s0", "s1", "s2", "s3"}
        assert rep["cost"]["predicted_state_bytes"] > 0
        skew = plane.skew_report()
        assert skew["total_rows"] == 300
        assert skew["imbalance"] >= 1.0

    def test_cost_report_is_fleet_priced(self):
        from siddhi_tpu.analysis.cost import compute_cost
        mgr = SiddhiManager()
        plane = mgr.create_siddhi_app_runtime(SHARDED_APP)
        ctx = plane.shards[0].ctx
        serial_rep = compute_cost(SERIAL_APP, batch_size=ctx.batch_size,
                                  group_capacity=ctx.group_capacity)
        try:
            assert plane.cost_report["predicted_state_bytes"] \
                == 4 * serial_rep.state_bytes
            assert any("shard fleet" in n
                       for n in plane.cost_report["notes"])
        finally:
            plane.shutdown()

    def test_manager_error_store_fans_out_to_shards(self):
        from siddhi_tpu.state.error_store import InMemoryErrorStore
        mgr = SiddhiManager()
        plane = mgr.create_siddhi_app_runtime(SHARDED_APP)
        store = InMemoryErrorStore()
        mgr.set_error_store(store)
        try:
            for srt in plane.shards:
                assert srt.ctx.error_store is store
        finally:
            plane.shutdown()

    def test_upgrade_refused_on_plane(self):
        mgr = SiddhiManager()
        plane = mgr.create_siddhi_app_runtime(SHARDED_APP)
        try:
            with pytest.raises(SiddhiAppCreationError,
                               match="sharded app"):
                mgr.upgrade(SHARDED_APP)
        finally:
            plane.shutdown()

    def test_prometheus_plane_families(self):
        from siddhi_tpu.telemetry.prometheus import render_manager
        mgr = SiddhiManager()
        plane, _ = _run(mgr, SHARDED_APP, _rows(200), shutdown=False)
        text = render_manager(mgr)
        plane.shutdown()
        assert 'siddhi_shard_count{app="PlaneApp"} 4' in text
        assert 'siddhi_shard_epoch{app="PlaneApp"} 0' in text
        assert 'siddhi_shard_routed_rows_total{app="PlaneApp",shard="s0"}' \
            in text
        assert "siddhi_shard_imbalance_ratio" in text
        # per-shard runtime families exist under the replica names
        assert 'app="PlaneApp@s0"' in text
