"""A @PrimaryKey table on the device index (core/table_index.py): the
SiddhiQL guide's `update or insert` and `Join (Table)` over a device
registry, held bit for bit and in order to the plain per-event reference
(tests/registry_reference.py) at small sizes on seeded data; the plan's
choice and its lint text (SL120), and every form that stays on the [B, C]
path; and the parent's faults on this app, as regression tests."""

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.analysis import analyze
from siddhi_tpu.errors import SiddhiAppCreationError

from .registry_reference import Registry

TYPES = ("server-room", "office", "lab", "storage")

STREAMS = """
{async_}define stream DeviceStream (deviceID long, roomNo int, type string, timestamp long);
{async_}define stream TempStream (deviceID long, roomNo int, temp double, timestamp long);
"""
TABLE = """
@PrimaryKey('deviceID') @capacity(rows = '{cap}')
define table DeviceTable (deviceID long, roomNo int, type string, updated long);
"""
REGISTER = """
@info(name = 'register')
from DeviceStream select deviceID, roomNo, type, timestamp as updated
{write};
"""
UPSERT = ("update or insert into DeviceTable "
          "on DeviceTable.deviceID == deviceID")
ENRICH = """
@info(name = 'enrich')
from TempStream join DeviceTable on TempStream.deviceID == DeviceTable.deviceID{on}
select TempStream.deviceID as deviceID, DeviceTable.roomNo as roomNo,
       DeviceTable.type as roomType, TempStream.temp as temp,
       TempStream.timestamp as timestamp, DeviceTable.updated as regStamp
{having}
insert into ServerRoomTempStream;
"""
HAVING = "having roomType == 'server-room'"
ON_TYPE = " and DeviceTable.type == 'server-room'"


def app(cap=1024, write=UPSERT, having=HAVING, on="", async_="",
        table=TABLE):
    return (STREAMS.format(async_=async_) + table.format(cap=cap)
            + REGISTER.format(write=write)
            + ENRICH.format(on=on, having=having))


class Fleet:
    """Seeded devices (63-bit ids), their rooms, registrations and
    readings; every event stamped with a global index."""

    def __init__(self, seed, keys):
        self.rng = np.random.default_rng(seed)
        self.ids = self.rng.choice(np.arange(-2**62, 2**62, 2**40 + 7),
                                   keys, replace=False).astype(np.int64)
        self.rooms = self.rng.integers(0, 100, keys).astype(np.int32)
        self.stamp = 0

    def _stamps(self, n):
        out = list(range(self.stamp, self.stamp + n))
        self.stamp += n
        return out

    def registrations(self, n, devices=None):
        d = self.rng.integers(0, self.ids.size, n) if devices is None \
            else np.asarray(devices)
        kinds = self.rng.integers(0, len(TYPES), n)
        return [(int(self.ids[i]), int(self.rooms[i]), TYPES[k], s)
                for i, k, s in zip(d.tolist(), kinds.tolist(),
                                   self._stamps(n))]

    def readings(self, n, devices=None):
        d = self.rng.integers(0, self.ids.size, n) if devices is None \
            else np.asarray(devices)
        temps = self.rng.integers(10 * 64, 40 * 64, n) / 64.0
        return [(int(self.ids[i]), int(self.rooms[i]), float(t), s)
                for i, t, s in zip(d.tolist(), temps.tolist(),
                                   self._stamps(n))]


class Engine:
    def __init__(self, text, batch):
        self.rt = SiddhiManager().create_siddhi_app_runtime(
            text, batch_size=batch)
        self.rows = []
        self.rt.add_callback("ServerRoomTempStream", lambda evs: self.rows.extend(
            tuple(e.data) for e in evs))
        self.rt.start()

    def send(self, stream, rows):
        self.rt.get_input_handler(stream).send_batch(rows)
        self.rt.flush()

    @property
    def table(self):
        return self.rt.tables["DeviceTable"]

    def table_rows(self):
        return sorted(self.table.all_rows())

    def counters(self):
        return self.rt.statistics_report()["tables"]["DeviceTable"]


def reference_rows(reg: Registry):
    return sorted((k, *v) for k, v in reg.rows.items())


def run_both(text, batch, plan, insert_mode=False):
    """Feed the engine and the reference the same batches, in order; a
    batch is ("reg" | "read", rows)."""
    eng = Engine(text, batch)
    reg = Registry()
    want = []
    eng.hits = 0
    for kind, rows in plan:
        if kind == "reg":
            eng.send("DeviceStream", rows)
            for r in rows:
                (reg.insert if insert_mode else reg.upsert)(*r)
        else:
            eng.send("TempStream", rows)
            for dev, _, temp, stamp in rows:
                eng.hits += dev in reg.rows
                row = reg.enrich(dev, float(np.float32(temp)), stamp)
                if row is not None:
                    want.append(row)
    return eng, reg, want


def assert_same(eng, reg, want):
    got = [(d, r, t, float(np.float32(temp)), ts, u)
           for d, r, t, temp, ts, u in eng.rows]
    assert got == want  # bit for bit and in reading order
    assert eng.table_rows() == reference_rows(reg)


# --------------------------------------------------- engine vs reference


@pytest.mark.parametrize("seed,keys,batch,cap", [
    (11, 50, 8, 64), (12, 120, 32, 256), (13, 500, 256, 1024),
    (14, 300, 64, 512)])
def test_upsert_and_join_match_the_per_event_reference(seed, keys, batch,
                                                      cap):
    fleet = Fleet(seed, keys)
    plan = []
    for _ in range(6):
        # repeated keys within a batch: few keys, many events
        plan.append(("reg", fleet.registrations(batch)))
        plan.append(("read", fleet.readings(batch)))
    eng, reg, want = run_both(app(cap=cap), batch, plan)
    assert_same(eng, reg, want)
    c = eng.counters()
    n_reg = 6 * batch
    assert c["inserted"] == len(reg.rows) == c["rows"]
    assert c["inserted"] + c["updated"] == n_reg
    assert c["dropped"] == 0 and c["duplicates"] == 0
    assert c["probes"] == 6 * batch
    assert c["hits"] == eng.hits
    assert c["capacity"] == cap and c["steps"] == 6


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_repeated_keys_in_one_batch_last_event_wins(seed):
    fleet = Fleet(seed, 4)
    rows = fleet.registrations(64)  # 4 devices, 16 events each
    eng, reg, want = run_both(app(cap=64), 64, [("reg", rows)])
    assert_same(eng, reg, want)
    last = {}
    for r in rows:
        last[r[0]] = r
    assert eng.table_rows() == sorted(last.values())


@pytest.mark.parametrize("seed", [31, 32])
def test_plain_insert_first_event_wins_and_the_rest_are_counted(seed):
    fleet = Fleet(seed, 10)
    plan = [("reg", fleet.registrations(40)), ("reg", fleet.registrations(40)),
            ("read", fleet.readings(40))]
    eng, reg, want = run_both(app(cap=64, write="insert into DeviceTable"),
                              40, plan, insert_mode=True)
    assert_same(eng, reg, want)
    assert eng.table.dropped_duplicates == reg.duplicates == 80 - 10
    assert eng.counters()["duplicates"] == reg.duplicates


def test_an_update_of_an_existing_row():
    fleet = Fleet(41, 3)
    first = fleet.registrations(3, devices=[0, 1, 2])
    second = [(first[1][0], first[1][1], "server-room" if first[1][2] !=
               "server-room" else "lab", fleet._stamps(1)[0])]
    plan = [("reg", first), ("reg", second),
            ("read", fleet.readings(3, devices=[0, 1, 2]))]
    eng, reg, want = run_both(app(cap=8), 8, plan)
    assert_same(eng, reg, want)
    c = eng.counters()
    assert (c["inserted"], c["updated"], c["rows"]) == (3, 1, 3)


def test_a_full_table_drops_and_counts_and_never_aliases():
    cap = 64
    fleet = Fleet(51, 100)
    rows = fleet.registrations(100, devices=np.arange(100))
    eng = Engine(app(cap=cap), 128)
    eng.send("DeviceStream", rows)
    eng.send("DeviceStream", fleet.registrations(10, devices=np.arange(90, 100)))
    c = eng.counters()
    assert c["rows"] == cap and c["inserted"] == cap
    assert c["dropped"] == (100 - cap) + 10
    # the first `cap` devices to arrive hold their own rows, nothing else
    assert eng.table_rows() == sorted(rows[:cap])
    assert "table:DeviceTable.table_rows_dropped" in \
        eng.rt.statistics_report()["overflow"]


def test_join_hit_miss_and_a_reading_right_after_its_registration():
    fleet = Fleet(61, 4)
    regs = [(int(fleet.ids[0]), 1, "server-room", 0),
            (int(fleet.ids[1]), 2, "office", 1)]
    fleet.stamp = 2
    plan = [("reg", regs),
            ("read", fleet.readings(3, devices=[0, 1, 2])),  # hit, -, miss
            ("reg", [(int(fleet.ids[1]), 2, "server-room", 10)]),
            ("read", fleet.readings(2, devices=[1, 2]))]
    eng, reg, want = run_both(app(cap=16), 8, plan)
    assert_same(eng, reg, want)
    assert [r[0] for r in eng.rows] == [int(fleet.ids[0]), int(fleet.ids[1])]
    assert eng.rows[-1][5] == 10  # enriched from the registration before it
    c = eng.counters()
    assert (c["probes"], c["hits"]) == (5, 3)


@pytest.mark.parametrize("form", ["having", "on"])
@pytest.mark.parametrize("primary_key", [True, False])
def test_string_comparisons_against_table_attributes(form, primary_key):
    table = TABLE if primary_key else TABLE.replace(
        "@PrimaryKey('deviceID') ", "")
    text = app(cap=64, table=table,
               having=HAVING if form == "having" else "",
               on=ON_TYPE if form == "on" else "")
    fleet = Fleet(71, 20)
    plan = [("reg", fleet.registrations(16, devices=np.arange(16))),
            ("read", fleet.readings(32))]
    eng, reg, want = run_both(text, 32, plan)
    assert_same(eng, reg, want)
    assert (eng.table.index_key is not None) == primary_key


def test_both_streams_through_async_ingress():
    text = app(cap=256, async_="@Async(buffer.size='64', workers='2')\n")
    fleet = Fleet(81, 200)
    eng = Engine(text, 64)
    reg, want = Registry(), []
    for _ in range(4):
        rows = fleet.registrations(64)
        eng.rt.get_input_handler("DeviceStream").send_batch(rows)
        eng.rt.drain(timeout=60)
        for r in rows:
            reg.upsert(*r)
        rows = fleet.readings(64)
        eng.rt.get_input_handler("TempStream").send_batch(rows)
        eng.rt.drain(timeout=60)
        for dev, _, temp, stamp in rows:
            row = reg.enrich(dev, float(np.float32(temp)), stamp)
            if row is not None:
                want.append(row)
    pipes = eng.rt.statistics_report()["ingress_pipeline"]
    assert {"DeviceStream", "TempStream"} <= set(pipes)
    assert_same(eng, reg, want)
    eng.rt.shutdown()


def test_membership_and_all_rows_read_the_indexed_table():
    text = app(cap=64) + """
    @info(name = 'known')
    from TempStream[TempStream.deviceID == DeviceTable.deviceID in DeviceTable]
    select deviceID insert into Known;"""
    fleet = Fleet(91, 10)
    eng = Engine(text, 16)
    got = []
    eng.rt.add_callback("Known", lambda evs: got.extend(e.data[0] for e in evs))
    eng.send("DeviceStream", fleet.registrations(5, devices=np.arange(5)))
    eng.send("TempStream", fleet.readings(10, devices=np.arange(10)))
    assert got == [int(i) for i in fleet.ids[:5]]


def test_a_write_fetches_nothing_until_the_64th(monkeypatch):
    fleet = Fleet(101, 50)
    eng = Engine(app(cap=64), 16)
    fetched = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetched.append(1) or real(x))
    for _ in range(63):
        eng.send("DeviceStream", fleet.registrations(16))
    assert not fetched
    eng.send("DeviceStream", fleet.registrations(16))
    assert len(fetched) == 1
    span = eng.table.cells.snapshot()
    assert span["write"]["batches"] == 64
    assert span["drop_sync"]["batches"] == 1


def test_the_write_program_is_warmed_and_named():
    eng = Engine(app(cap=64), 16)
    eng.rt.warmup((16,))
    names = {fn.__name__ for fn in eng.rt.query_runtimes[
        "register"].table_executor._fns.values()}
    assert names == {"table_write_DeviceTable"}


# ---------------------------------------------------- the plan and SL120


def _sl120(text):
    return [d.message for d in analyze(text).diagnostics
            if d.rule_id == "SL120"]


def test_the_registry_is_indexed_and_says_so():
    text = app(cap=1024)
    (msg,) = _sl120(text)
    assert "device index of its key: 1024 rows" in msg
    eng = Engine(text, 8)
    assert eng.table.index_key == "deviceID"
    assert type(eng.rt.query_runtimes["register"].table_executor).__name__ \
        == "IndexedTableWriter"


COLUMNAR = {
    "composite key": (TABLE.replace("'deviceID'", "'deviceID', 'roomNo'"),
                      UPSERT, "composite"),
    "set reads the table": (TABLE, UPSERT.replace(
        " on", " set DeviceTable.updated = DeviceTable.updated + 1 on"),
        "reads the table's own attributes"),
    "delete": (TABLE, "delete DeviceTable on DeviceTable.deviceID == "
               "deviceID", "deletes from it"),
    "update without insert": (TABLE, "update DeviceTable on "
                              "DeviceTable.deviceID == deviceID",
                              "updates it without insert"),
    "non-equality on": (TABLE, UPSERT.replace("==", ">="), "`on` is not"),
    "conjunct in on": (TABLE, UPSERT + " and DeviceTable.roomNo == roomNo",
                       "`on` is not"),
    "sets the key": (TABLE, UPSERT.replace(
        " on", " set DeviceTable.deviceID = deviceID on"), "sets the key"),
    "double key": (TABLE.replace("deviceID long, roomNo", "deviceID double, "
                                 "roomNo"), UPSERT, "not an int, long"),
}


@pytest.mark.parametrize("form", list(COLUMNAR))
def test_every_other_form_stays_on_the_columnar_path(form):
    table, write, why = COLUMNAR[form]
    text = app(cap=64, table=table, write=write)
    (msg,) = _sl120(text)
    assert "stays on the [B, C] path" in msg and why in msg, msg
    eng = Engine(text, 8)
    assert eng.table.index_key is None
    fleet = Fleet(111, 8)
    eng.send("DeviceStream", fleet.registrations(8))  # still runs


def test_the_columnar_path_is_refused_where_its_masks_exceed_the_device():
    text = app(cap=1 << 20, table=TABLE.replace("'deviceID'",
                                                "'deviceID', 'roomNo'"))
    with pytest.raises(SiddhiAppCreationError, match=r"\[B, C\] masks take"):
        SiddhiManager().create_siddhi_app_runtime(text, batch_size=131072)


def test_the_indexed_registry_builds_at_the_deployments_size():
    eng = Engine(app(cap=1 << 20), 131072)
    assert eng.table.index_key == "deviceID"
    assert eng.table.index_state().slots.rows.shape == (1 << 17, 128)


# ------------------------------------------------- the parent's faults


def test_update_or_insert_without_a_primary_key_keeps_one_row_a_key():
    text = """define stream S (id int, v int);
    define table T (id int, v int);
    from S select id, v update or insert into T on T.id == id;"""
    rt = SiddhiManager().create_siddhi_app_runtime(text)
    rt.start()
    rt.get_input_handler("S").send_batch([(1, 10), (1, 20)])
    rt.flush()
    assert rt.tables["T"].all_rows() == [(1, 20)]


def test_update_or_insert_on_a_primary_key_keeps_the_last_event():
    text = """define stream S (id int, v int);
    @PrimaryKey('id') define table T (id int, v int);
    from S select id, v update or insert into T on T.id == id;"""
    rt = SiddhiManager().create_siddhi_app_runtime(text)
    rt.start()
    rt.get_input_handler("S").send_batch([(1, 10), (1, 20)])
    rt.flush()
    assert rt.tables["T"].all_rows() == [(1, 20)]
    assert rt.tables["T"].dropped_duplicates == 0


def test_two_registrations_of_a_device_in_one_batch_the_second_wins():
    eng = Engine(app(cap=64, having=""), 8)
    eng.send("DeviceStream", [(7, 1, "server-room", 1), (7, 1, "office", 2)])
    eng.send("TempStream", [(7, 1, 30.0, 3)])
    assert eng.rows == [(7, 1, "office", 30.0, 3, 2)]


def test_sl106_does_not_warn_on_a_stream_joined_to_a_table():
    assert not [d for d in analyze(app()).diagnostics
                if d.rule_id == "SL106"]
    raw = """define stream L (k int, x double);
    define stream R (k int, y double);
    from L join R on L.k == R.k select L.x as x, R.y as y insert into Out;"""
    assert [d for d in analyze(raw).diagnostics if d.rule_id == "SL106"]


def test_an_on_demand_delete_goes_past_the_index_which_is_made_anew():
    fleet = Fleet(121, 6)
    eng = Engine(app(cap=8, having=""), 8)
    rows = fleet.registrations(6, devices=np.arange(6))
    eng.send("DeviceStream", rows)
    gone = rows[2][0]
    eng.rt.query(f"delete DeviceTable on DeviceTable.deviceID == {gone}L")
    assert eng.table_rows() == sorted(r for r in rows if r[0] != gone)
    eng.send("DeviceStream", fleet.registrations(2, devices=[2, 6 % 6]))
    eng.send("TempStream", fleet.readings(6, devices=np.arange(6)))
    assert [r[0] for r in eng.rows] == [int(i) for i in fleet.ids[:6]]
    assert eng.counters()["rows"] == 6  # the deleted key's row came back


def test_the_cost_model_prices_the_index_as_the_runtime_holds_it():
    from siddhi_tpu.analysis.cost import (compute_cost,
                                          measure_runtime_state_bytes)
    eng = Engine(app(cap=4096), 256)
    report = compute_cost(eng.rt.app, batch_size=256).to_dict()
    (table,) = [e for e in report["elements"]
                if e["element"] == "DeviceTable"]
    assert table["exact"]
    assert table["state_bytes"] == \
        measure_runtime_state_bytes(eng.rt)["DeviceTable"]
