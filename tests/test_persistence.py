"""Checkpoint / restore tests.

Mirrors the reference's managment/PersistenceTestCase.java: run a stateful
query, persist, create a fresh runtime, restore, continue sending — aggregate
state must carry over.
"""

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.state.persistence import (
    FileSystemPersistenceStore,
    InMemoryPersistenceStore,
)


pytestmark = pytest.mark.smoke

APP = ("@app:name('PersistApp')\n"
       "define stream S (symbol string, price float);\n"
       "@info(name = 'q1')\n"
       "from S select symbol, sum(price) as total group by symbol "
       "insert into OutStream;")


def build(store, got):
    manager = SiddhiManager()
    manager.set_persistence_store(store)
    rt = manager.create_siddhi_app_runtime(APP, batch_size=4)
    rt.add_callback("OutStream", lambda evs: got.extend(e.data for e in evs))
    rt.start()
    return rt


class TestPersistRestore:
    def _roundtrip(self, store):
        got1 = []
        rt1 = build(store, got1)
        h = rt1.get_input_handler("S")
        h.send(("IBM", 10.0))
        h.send(("IBM", 20.0))
        rt1.flush()
        assert got1[-1] == ("IBM", 30.0)
        rev = rt1.persist()
        assert rev

        # fresh runtime: state restored, aggregation continues from 30.0
        got2 = []
        rt2 = build(store, got2)
        restored = rt2.restore_last_revision()
        assert restored == rev
        rt2.get_input_handler("S").send(("IBM", 5.0))
        rt2.flush()
        assert got2[-1] == ("IBM", 35.0)

    def test_in_memory_store(self):
        self._roundtrip(InMemoryPersistenceStore())

    def test_filesystem_store(self, tmp_path):
        self._roundtrip(FileSystemPersistenceStore(str(tmp_path)))

    def test_snapshot_restore_bytes(self):
        got = []
        manager = SiddhiManager()
        rt = manager.create_siddhi_app_runtime(APP, batch_size=4)
        rt.add_callback("OutStream", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        h.send(("A", 1.0))
        rt.flush()
        blob = rt.snapshot()
        h.send(("A", 2.0))
        rt.flush()
        assert got[-1] == ("A", 3.0)
        rt.restore(blob)  # back to sum=1.0
        h.send(("A", 2.0))
        rt.flush()
        assert got[-1] == ("A", 3.0)

    def test_window_state_persisted(self):
        app = ("@app:name('WinApp')\n"
               "define stream S (k string, v int);\n"
               "from S#window.lengthBatch(3) select sum(v) as s "
               "insert into OutStream;")
        store = InMemoryPersistenceStore()
        got1 = []
        manager = SiddhiManager()
        manager.set_persistence_store(store)
        rt1 = manager.create_siddhi_app_runtime(app, batch_size=4)
        rt1.add_callback("OutStream", lambda evs: got1.extend(e.data for e in evs))
        rt1.start()
        h = rt1.get_input_handler("S")
        h.send(("a", 1)); h.send(("b", 2))
        rt1.flush()
        assert got1 == []  # batch of 3 not complete
        rt1.persist()

        got2 = []
        manager2 = SiddhiManager()
        manager2.set_persistence_store(store)
        rt2 = manager2.create_siddhi_app_runtime(app, batch_size=4)
        rt2.add_callback("OutStream", lambda evs: got2.extend(e.data for e in evs))
        rt2.start()
        rt2.restore_last_revision()
        rt2.get_input_handler("S").send(("c", 4))
        rt2.flush()
        # flush emits per-event running sums over the restored window: the
        # final lane is 1+2 (restored) + 4
        assert got2[-1] == (7,)

    def test_pattern_snapshot_without_armed0_ts_restores(self):
        # round-3 builds pickled PatternState without the armed0_ts field;
        # restore must tolerate the missing leaf (re-armed from the current
        # runtime build) instead of failing (advisor round-4 low finding)
        import pickle

        from siddhi_tpu.core.pattern_runtime import PatternState

        app = ("define stream A (x int); define stream B (x int);\n"
               "@info(name='p') from e1=A -> e2=B "
               "select e1.x as ax, e2.x as bx insert into Out;")
        got = []
        manager = SiddhiManager()
        rt = manager.create_siddhi_app_runtime(app, batch_size=4)
        rt.add_callback("Out", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        rt.get_input_handler("A").send((1,))
        rt.flush()
        blob = rt.snapshot()

        # simulate the round-3 wire format: no armed0_ts / gate0_seq on
        # PatternState and no origin on the PendingTables
        from siddhi_tpu.core.pattern_runtime import PendingTable
        snap = pickle.loads(blob)
        st = snap["queries"]["p"]
        assert isinstance(st, PatternState)
        old_pending = tuple(PendingTable(*tuple(p)[:8]) for p in st.pending)
        snap["queries"]["p"] = PatternState(
            old_pending, *tuple(st)[1:5])
        assert snap["queries"]["p"].armed0_ts is None
        assert snap["queries"]["p"].gate0_seq is None
        assert old_pending[0].origin is None
        old_blob = pickle.dumps(snap)

        rt.restore(old_blob)
        rt.get_input_handler("B").send((2,))
        rt.flush()
        assert got[-1] == (1, 2)

    def test_last_revision_after_multiple_persists_and_torn_tmp(
            self, tmp_path):
        """restore_last_revision must pick the NEWEST whole revision even
        when a crash left a torn tmp file behind (FileSystemPersistenceStore
        writes fsync'd tmp+rename; an abandoned `.tmp` is never a
        candidate)."""
        store = FileSystemPersistenceStore(str(tmp_path))
        got1 = []
        rt1 = build(store, got1)
        h = rt1.get_input_handler("S")
        h.send(("IBM", 10.0))
        rt1.flush()
        rev1 = rt1.persist()
        h.send(("IBM", 20.0))
        rt1.flush()
        rev2 = rt1.persist()
        assert rev2 > rev1
        # simulate a crash mid-save AFTER rev2: a torn tmp with a name that
        # would sort last if it were ever considered
        d = tmp_path / "PersistApp"
        (d / ".9999999999999_PersistApp.tmp").write_bytes(b"half a snap")
        got2 = []
        rt2 = build(store, got2)
        assert rt2.restore_last_revision() == rev2
        rt2.get_input_handler("S").send(("IBM", 5.0))
        rt2.flush()
        assert got2[-1] == ("IBM", 35.0)  # rev2's 30.0 + 5.0

    def test_save_replaces_tmp_atomically(self, tmp_path):
        store = FileSystemPersistenceStore(str(tmp_path))
        store.save("A", "1_A", b"snap")
        assert sorted(f for f in (tmp_path / "A").iterdir()) == \
            [tmp_path / "A" / "1_A"]  # no tmp residue
        assert store.load("A", "1_A") == b"snap"

    def test_wrong_app_rejected(self):
        from siddhi_tpu.errors import CannotRestoreStateError
        manager = SiddhiManager()
        rt = manager.create_siddhi_app_runtime(APP, batch_size=4)
        other = manager.create_siddhi_app_runtime(
            "@app:name('Other')\ndefine stream S (x int);\n"
            "from S select x insert into Out2;", batch_size=4)
        blob = other.snapshot()
        with pytest.raises(CannotRestoreStateError):
            rt.restore(blob)

    LB_APP = ("@app:name('LbApp')\n"
              "define stream S (k string, p double, v long);\n"
              "@info(name='q') from S#window.lengthBatch(5) "
              "select k, sum(p) as total, sum(v) as vol, count() as n "
              "group by k insert all events into Out;")

    def _lb_runtime(self, got):
        rt = SiddhiManager().create_siddhi_app_runtime(self.LB_APP,
                                                       batch_size=4)
        rt.add_callback("Out", lambda evs: got.extend(
            (e.is_expired, e.data) for e in evs))
        rt.start()
        return rt

    ROWS = [(k, 0.5 * i, 2**40 + i) for i, k in enumerate("abacbbcaabcabcc")]

    def test_length_batch_packed_ring_round_trips_mid_window(self):
        # the ring is one packed u32 matrix: a snapshot taken with a partial
        # bucket and a previous flush in it must restore word for word, and
        # the restored runtime must go on exactly as the uninterrupted one
        import pickle

        import numpy as np

        from siddhi_tpu.ops.windows import LengthBatchState

        whole, got1, got2 = [], [], []
        rt0 = self._lb_runtime(whole)
        for row in self.ROWS:
            rt0.get_input_handler("S").send(row)
        rt0.flush()

        rt1 = self._lb_runtime(got1)
        for row in self.ROWS[:8]:  # one flush done, three rows pending
            rt1.get_input_handler("S").send(row)
        rt1.flush()
        blob = rt1.snapshot()
        wstate = pickle.loads(blob)["queries"]["q"][0]
        assert isinstance(wstate, LengthBatchState)
        assert wstate.ring.dtype == np.uint32 and wstate.ring.ndim == 2
        assert (int(wstate.appended), int(wstate.flushed)) == (8, 5)

        rt2 = self._lb_runtime(got2)
        rt2.restore(blob)
        for a, b in zip(rt1.query_runtimes["q"].state[0],
                        rt2.query_runtimes["q"].state[0]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for row in self.ROWS[8:]:
            rt2.get_input_handler("S").send(row)
        rt2.flush()
        # the restored previous flush expired: its groups ran down to zero
        assert any(data[3] == 0 for _, data in got2)
        assert got1 + got2 == whole

    def test_snapshot_with_per_column_ring_is_refused(self):
        # a revision written before lengthBatch moved to the packed ring
        # holds BatchState(ring_cols=..., ring_ts=...): refuse it, never
        # assign its leaves to the new state
        import pickle

        import numpy as np

        from siddhi_tpu.errors import CannotRestoreStateError
        from siddhi_tpu.ops.windows import BatchState

        got = []
        rt = self._lb_runtime(got)
        for row in self.ROWS[:8]:
            rt.get_input_handler("S").send(row)
        rt.flush()
        snap = pickle.loads(rt.snapshot())
        wstate, sstate, rstate = snap["queries"]["q"]
        C = wstate.ring.shape[1]
        old = BatchState(
            ring_cols={"k": np.zeros(C, np.int32), "p": np.zeros(C, np.float32),
                       "v": np.zeros(C, np.int64)},
            ring_ts=np.zeros(C, np.int64),
            appended=np.int64(8), flushed=np.int64(5),
            prev_start=np.int64(0), epoch_base=np.int64(0),
            has_base=np.bool_(False), wm=np.int64(-(2**62)))
        snap["queries"]["q"] = (old, sstate, rstate)
        before = [np.asarray(x).copy() for x in rt.query_runtimes["q"].state[0]]
        with pytest.raises(CannotRestoreStateError):
            rt.restore(pickle.dumps(snap))
        for a, b in zip(before, rt.query_runtimes["q"].state[0]):
            assert np.array_equal(a, np.asarray(b))


class TestIncrementalFileSystemStore:
    """Reference: IncrementalFileSystemPersistenceStore.java:37 — delta
    revisions with periodic full re-base."""

    def _build(self, manager, app):
        rt = manager.create_siddhi_app_runtime(app, batch_size=4)
        rt.start()
        return rt

    def test_delta_chain_restores(self, tmp_path):
        from siddhi_tpu.state.persistence import IncrementalFileSystemPersistenceStore
        app = ("@app:name('IncApp')\n"
               "define stream S (k string, v long);\n"
               "@info(name='q') from S select k, sum(v) as total group by k "
               "insert into Out;")
        store = IncrementalFileSystemPersistenceStore(str(tmp_path))
        manager = SiddhiManager()
        manager.set_persistence_store(store)
        rt = self._build(manager, app)
        h = rt.get_input_handler("S")
        revs = []
        for i in range(4):
            h.send(("a", i + 1))
            rt.flush()
            revs.append(rt.persist())
        # later revisions are deltas: strictly smaller than the full base
        import os
        d = tmp_path / "IncApp"
        sizes = {r: os.path.getsize(d / r) for r in revs}
        assert sizes[revs[1]] < sizes[revs[0]]

        rt2 = self._build(SiddhiManager(), app)
        rt2.persistence_store = store
        rt2.restore_revision(revs[3])
        got = []
        rt2.add_query_callback("q", lambda ts, i, r: got.extend(i or []))
        rt2.get_input_handler("S").send(("a", 10))
        rt2.flush()
        # restored running sum 1+2+3+4 = 10, plus 10
        assert got[-1].data[1] == 20

    def test_intermediate_revision_restores(self, tmp_path):
        from siddhi_tpu.state.persistence import IncrementalFileSystemPersistenceStore
        app = ("@app:name('IncApp2')\n"
               "define stream S (k string, v long);\n"
               "@info(name='q') from S select k, sum(v) as total group by k "
               "insert into Out;")
        store = IncrementalFileSystemPersistenceStore(str(tmp_path))
        manager = SiddhiManager()
        manager.set_persistence_store(store)
        rt = self._build(manager, app)
        h = rt.get_input_handler("S")
        revs = []
        for i in range(3):
            h.send(("a", i + 1))
            rt.flush()
            revs.append(rt.persist())
        rt2 = self._build(SiddhiManager(), app)
        rt2.persistence_store = store
        rt2.restore_revision(revs[1])  # middle delta: base + one delta
        got = []
        rt2.add_query_callback("q", lambda ts, i, r: got.extend(i or []))
        rt2.get_input_handler("S").send(("a", 0))
        rt2.flush()
        assert got[-1].data[1] == 3  # 1+2 restored

    def test_full_rebase_every_n(self, tmp_path):
        from siddhi_tpu.state.persistence import IncrementalFileSystemPersistenceStore
        app = ("@app:name('IncApp3')\n"
               "define stream S (k string, v long);\n"
               "from S select k, sum(v) as t group by k insert into Out;")
        store = IncrementalFileSystemPersistenceStore(str(tmp_path), full_every=2)
        manager = SiddhiManager()
        manager.set_persistence_store(store)
        rt = self._build(manager, app)
        h = rt.get_input_handler("S")
        import pickle
        revs = []
        for i in range(4):
            h.send(("a", 1))
            rt.flush()
            revs.append(rt.persist())
        kinds = []
        for r in revs:
            with open(tmp_path / "IncApp3" / r, "rb") as f:
                kinds.append(pickle.load(f)["kind"])
        assert kinds == ["full", "delta", "full", "delta"]


class TestDeviceDeltaPersist:
    """VERDICT r3 item 7 (first half): persist() must not re-read device
    state that no batch touched — object identity of the state pytrees is
    the change log (every jitted step replaces its state)."""

    APP = ("define stream S (sym string, v long);\n"
           "@info(name='q') from S#window.length(100) "
           "select sym, sum(v) as total group by sym insert into Out;")

    def _runtime(self, store):
        rt = SiddhiManager().create_siddhi_app_runtime(self.APP, batch_size=8)
        rt.persistence_store = store
        rt.start()
        return rt

    def test_idle_persist_fetches_nothing_and_ships_no_leaves(self, tmp_path):
        import pickle

        import siddhi_tpu.state.persistence as P
        store = P.IncrementalFileSystemPersistenceStore(str(tmp_path))
        rt = self._runtime(store)
        h = rt.get_input_handler("S")
        h.send(("a", 1))
        h.send(("b", 2))
        rt.flush()
        rt.persist()

        calls = []
        orig = P._to_host
        P._to_host = lambda t: (calls.append(1), orig(t))[1]
        try:
            rev2 = rt.persist()  # nothing ran since the last persist
        finally:
            P._to_host = orig
        assert calls == [], "idle persist still fetched device state"
        app_dir = tmp_path / rt.app.name
        payload = pickle.loads((app_dir / rev2).read_bytes())
        assert payload["kind"] == "delta"
        assert payload["leaves"] == {}

    def test_active_persist_fetches_and_restores(self, tmp_path):
        import siddhi_tpu.state.persistence as P
        store = P.IncrementalFileSystemPersistenceStore(str(tmp_path))
        rt = self._runtime(store)
        h = rt.get_input_handler("S")
        h.send(("a", 1))
        rt.flush()
        rt.persist()
        h.send(("a", 9))
        rt.flush()
        rev2 = rt.persist()  # state changed: delta carries the new leaves

        rt2 = self._runtime(store)
        rt2.restore_revision(rev2)
        got = []
        rt2.add_query_callback("q", lambda ts, i, r: got.extend(
            tuple(e.data) for e in i or []))
        rt2.get_input_handler("S").send(("a", 5))
        rt2.flush()
        assert got == [("a", 15)]
