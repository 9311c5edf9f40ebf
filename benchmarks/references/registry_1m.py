"""Plain reference for `registry_1m`: the SiddhiQL guide's table join and
update-or-insert over a device registry,

    from DeviceStream select deviceID, roomNo, type, timestamp as updated
    update or insert into DeviceTable on DeviceTable.deviceID == deviceID;
    from TempStream join DeviceTable
        on TempStream.deviceID == DeviceTable.deviceID
    select TempStream.deviceID as deviceID, DeviceTable.roomNo as roomNo,
           DeviceTable.type as roomType, TempStream.temp as temp,
           TempStream.timestamp as timestamp, DeviceTable.updated as regStamp
    having roomType == 'server-room' insert into ServerRoomTempStream;

Per event (`Registry`, a dict, no numpy, nothing of the program): a
registration replaces its device's row (`update or insert`: one row a key,
the last event wins); a reading gives a row exactly where its device has a
row of type 'server-room', with that row's room, type and stamp.

**What a reading sees depends on how many registrations the engine had
applied when its frame ran**, and the rows say it: every row carries the
stamp of the registration it was enriched from (`regStamp`). With one
registration producer the table at any step is the registry after some
prefix of that producer's events (its frames in number order: the parent's
warm-up frames, then the producer's accepted ones). For each delivered block
(one reading frame, one step) the replay takes the least prefix, no less
than the block before's, that explains EVERY reading of the frame: a row
exactly where the device's type there is 'server-room', with `roomNo`,
`roomType` and `regStamp` equal, in reading order. A block that no prefix
explains fails the run. Registration frames yield no block.

- `account(run)`: the table's counters first (`dropped` 0; `probes` equal to
  the readings sent; inserts and updates equal to the registrations sent);
  then every block through the replay, vectorised (`Replay`): its frame, its
  rows' order, `deviceID`, `temp` (bit for bit), `timestamp` and the
  registration each row names, and its prefix.
- `verify_sample(run, rng)`: >= 64 seeded blocks against the per-event
  `Registry`, fed every registration in order in one sweep: every row,
  every column, `roomType` decoded.
- `completed(run, lo, hi)`: a reading counts when its block is delivered in
  the span; a registration once a block delivered in the span has a prefix
  that holds it (as `pattern_ab` credits a trade when its row is out).
"""

from __future__ import annotations

import numpy as np

import record

DEVICES, READINGS = 0, 1  # the input streams, in the configuration's order
SAMPLE = 64  # blocks checked against the per-event loop, at least
WANTED = "server-room"
NUMERIC = ("deviceID", "roomNo", "temp", "timestamp", "regStamp")


# ------------------------------------------------- the per-event reference


class Registry:
    """A primary-key table of rows `(roomNo, type, updated)` by deviceID,
    one event per turn."""

    def __init__(self) -> None:
        self.rows: dict = {}
        self.duplicates = 0

    def upsert(self, device_id, room, kind, stamp) -> None:
        """`update or insert`: the event's row, whatever was there."""
        self.rows[device_id] = (room, kind, stamp)

    def upsert_many(self, device_ids, rows) -> None:
        """`upsert` of each (device_id, (room, kind, stamp)) in turn."""
        self.rows.update(zip(device_ids, rows))

    def insert(self, device_id, room, kind, stamp) -> None:
        """`insert into` a primary-key table: a key it holds is refused
        and counted."""
        if device_id in self.rows:
            self.duplicates += 1
        else:
            self.rows[device_id] = (room, kind, stamp)

    def enrich(self, device_id, temp, stamp, wanted=WANTED):
        """The row a reading gives, or None: an inner join on the key and
        `having roomType == wanted`."""
        row = self.rows.get(device_id)
        if row is None or row[1] != wanted:
            return None
        room, kind, updated = row
        return (device_id, room, kind, temp, stamp, updated)


# ------------------------------------------------------- the serialisation


def _accepted(run: dict) -> dict:
    """frame number -> rows the server accepted, for every frame that got a
    `200` (the producers') or was posted by the parent itself."""
    frames = run["frames"]
    ok = frames["status"] == 200
    out = dict(run["sent_extra"])
    out.update(zip(frames["frame"][ok].tolist(),
                   frames["accepted"][ok].tolist()))
    return out


def _types(run: dict) -> list:
    return list(run["events"].plans[DEVICES]["params"]["types"])


class Registrations:
    """The registration producer's events in the order the engine applied
    them: its accepted frames in number order, as flat arrays by position
    (device rank, type index), and the way from a stamp to its position."""

    def __init__(self, run: dict) -> None:
        events = run["events"]
        accepted = _accepted(run)
        self.frames = sorted(f for f in accepted
                             if events.source(f)[0] == DEVICES)
        self.rows = [events.plan_of(f)["rows"] for f in self.frames]
        self.start = {f: s for f, s in zip(
            self.frames, np.r_[0, np.cumsum(self.rows)[:-1]].tolist())}
        self.total = int(sum(self.rows))
        self.warm = sum(n for f, n in zip(self.frames, self.rows)
                        if f < events.warm_total)
        self.stride = events.stride
        cols = [events.frame_columns(f) for f in self.frames]
        self.device = np.concatenate(
            [c["device"] for c in cols] or [np.zeros(0, np.int64)]
        ).astype(np.int32)
        self.kind = np.concatenate(
            [c["type_code"] for c in cols] or [np.zeros(0, np.int32)]
        ).astype(np.int8)

    def positions(self, stamps: np.ndarray) -> np.ndarray:
        """Each stamp's position, -1 where it names no registration."""
        frames = np.array(self.frames, np.int64)
        if not frames.size:
            return np.full(stamps.size, -1, np.int64)
        rows = np.array(self.rows, np.int64)
        starts = np.r_[0, np.cumsum(rows)[:-1]]
        f, row = stamps // self.stride, stamps % self.stride
        k = np.minimum(np.searchsorted(frames, f), frames.size - 1)
        ok = (frames[k] == f) & (row < rows[k])
        return np.where(ok, starts[k] + row, -1)


class Replay:
    """The registry by device rank after a prefix of the registrations:
    each device's type (-1: no row) and the position of the registration
    that wrote it."""

    def __init__(self, regs: Registrations, devices: int) -> None:
        self.regs = regs
        self.prefix = 0
        self.kind = np.full(devices, -1, np.int8)
        self.pos = np.full(devices, -1, np.int64)

    def advance(self, to: int) -> None:
        a, z = self.prefix, min(to, self.regs.total)
        if z > a:
            d = self.regs.device[a:z][::-1]
            u, first = np.unique(d, return_index=True)
            last = (z - 1) - first  # the last registration of each device
            self.kind[u] = self.regs.kind[last]
            self.pos[u] = last
        self.prefix = max(self.prefix, z)

    def next_of(self, devices: np.ndarray, after: int) -> np.ndarray:
        """Per device, the position of its first registration at or after
        `after`; `total` where there is none."""
        out = np.full(devices.size, self.regs.total, np.int64)
        want = np.unique(devices)
        chunk = 1 << 20
        left = want
        at = after
        while left.size and at < self.regs.total:
            part = self.regs.device[at:at + chunk]
            hit = np.nonzero(np.isin(part, left))[0]
            if hit.size:
                d, first = np.unique(part[hit], return_index=True)
                found = dict(zip(d.tolist(), (at + hit[first]).tolist()))
                for i, dev in enumerate(devices.tolist()):
                    if dev in found and out[i] == self.regs.total:
                        out[i] = found[dev]
                left = np.setdiff1d(left, d)
            at += chunk
        return out


def _block_frame(block, stride: int) -> int:
    ts = block.timestamps
    if not block.count:
        return -1
    f = int(ts[0]) // stride
    return f if int(ts.max()) // stride == f else -1


def replay(run: dict) -> dict:
    """Every delivered block through the vectorised replay, once a run (kept
    in `run`): per block its frame, its prefix (None where none explains
    it) and what failed."""
    cached = run.get("registry_replay")
    if cached is not None:
        return cached
    events = run["events"]
    stride = events.stride
    regs = Registrations(run)
    types = _types(run)
    server = types.index(WANTED)
    devices = int(events.plans[READINGS]["params"]["keys"])
    state = Replay(regs, devices)
    accepted = _accepted(run)
    blocks = run["delivered"]["blocks"]
    out = {"frame": [], "prefix": [], "failures": [], "bad_events": 0,
           "regs": regs, "codes": set()}
    seen: set = set()
    for b, block in enumerate(blocks):
        f = _block_frame(block, stride)
        out["frame"].append(f)
        out["prefix"].append(None)
        if f < 0 or f not in accepted \
                or events.source(f)[0] != READINGS or f in seen:
            out["failures"].append(
                f"block {b} answers no single accepted reading frame ({f})")
            out["bad_events"] += block.count
            continue
        seen.add(f)
        why = _explain(block, f, events, regs, state, server)
        if isinstance(why, str):
            out["failures"].append(f"block {b} (frame {f}): {why}")
            out["bad_events"] += events.plan_of(f)["rows"]
            continue
        out["prefix"][-1] = state.prefix
        if block.count:
            out["codes"].update(np.unique(block.column("roomType")).tolist())
    out["unanswered"] = sorted(
        f for f in accepted
        if events.source(f)[0] == READINGS and f not in seen)
    out["final_prefix"] = state.prefix
    out["state"] = state
    run["registry_replay"] = out
    return out


def _explain(block, f, events, regs, state, server):
    """Advance `state` to the least prefix that explains the block, or say
    why none does."""
    stride = events.stride
    cols = events.frame_columns(f)
    n = events.plan_of(f)["rows"]
    ts = block.timestamps
    row = ts - f * stride
    if block.count and (np.any(np.diff(row) <= 0) or row[0] < 0
                        or row[-1] >= n):
        return "rows not in reading order, or not the frame's"
    dev = cols["device"][row]
    same = {
        "timestamp": np.array_equal(block.column("timestamp"), ts),
        "deviceID": np.array_equal(block.column("deviceID"),
                                   cols["deviceID"][row]),
        "roomNo": np.array_equal(block.column("roomNo"),
                                 cols["roomNo"][row]),
        "temp": np.array_equal(
            block.column("temp").astype(np.float32).view(np.int32),
            cols["temp"][row].astype(np.float32).view(np.int32)),
    }
    bad = [k for k, ok in same.items() if not ok]
    if bad:
        return f"columns {bad} are not the readings'"
    pos = regs.positions(block.column("regStamp"))
    if np.any(pos < 0):
        return "a row names no registration that was applied"
    if np.any(regs.device[pos] != dev) \
            or np.any(regs.kind[pos] != server):
        return ("a row names a registration of another device, or not of "
                f"type {WANTED!r}")
    lo = int(pos.max()) + 1 if pos.size else 0
    absent = np.ones(n, bool)
    absent[row] = False
    a_dev = cols["device"][absent]
    state.advance(max(lo, state.prefix))
    while True:
        if np.any(state.pos[dev] != pos):
            return ("no prefix holds every row's registration as its "
                    "device's latest")
        wrong = state.kind[a_dev] == server
        if not wrong.any():
            return None
        nxt = state.next_of(a_dev[wrong], state.prefix)
        if np.any(nxt >= regs.total):
            return (f"{int(wrong.sum())} readings in server rooms have no "
                    "row, whatever the prefix")
        state.advance(int(nxt.max()) + 1)


# ------------------------------------------------------- run.py's questions


def expected_output_rows(run: dict, sent_frames) -> int:
    """A lower bound: a row at least for every sent reading frame (a
    quarter of its readings are in server rooms)."""
    events = run["events"]
    return sum(1 for f in sent_frames if events.source(f)[0] == READINGS)


def completed(run: dict, lo_ns: int, hi_ns: int) -> float:
    """Events of both streams whose results reached the callback in
    [lo, hi): a measured reading with its frame's block, a measured
    registration with the first block whose prefix holds it."""
    rep = replay(run)
    frames = run["frames"]
    measured = set(frames["frame"][frames["status"] == 200].tolist())
    events = run["events"]
    regs = rep["regs"]
    t = run["delivered"]["enter_ns"]
    done = 0.0
    held = regs.warm
    for b, (f, prefix) in enumerate(zip(rep["frame"], rep["prefix"])):
        if prefix is None:
            continue
        inside = lo_ns <= t[b] < hi_ns if b < t.size else False
        if inside and f in measured:
            done += events.plan_of(f)["rows"]
        if prefix > held:
            if inside:
                done += prefix - held
            held = prefix
    return done


def _counters(run: dict) -> dict:
    tables = run["stats_end"].get("tables") or {}
    return tables.get("DeviceTable") or {}


def account(run: dict) -> dict:
    events, frames = run["events"], run["frames"]
    stats_end = run["stats_end"]
    accepted = _accepted(run)
    sent = [0, 0]
    for f, rows in accepted.items():
        sent[events.source(f)[0]] += rows
    table = _counters(run)
    keys = int(events.plans[DEVICES]["params"]["keys"])
    attempted = int(frames["rows"].sum())
    refused = int(frames["rows"][frames["status"] != 200].sum())
    first = {
        "table_counters_reported": bool(table),
        "table_dropped_zero": table.get("dropped") == 0,
        "probes_equal_readings_sent": table.get("probes") == sent[READINGS],
        "writes_equal_registrations_sent":
            table.get("inserted", 0) + table.get("updated", 0)
            == sent[DEVICES],
        "one_row_a_device": table.get("rows") == keys,
    }
    if not all(first.values()):
        return {"checks": first, "conserved": False,
                "failures": [f"counter check {k} failed: {table}"
                             for k, v in first.items() if not v][:3],
                "attempted": attempted, "failed": attempted,
                "detail": {"table": table, "sent": sent}}
    rep = replay(run)
    pipes = stats_end.get("ingress_pipeline") or {}
    rows_in = [(pipes.get(plan["stream"]) or {}).get("rows_in")
               for plan in events.plans]
    blocks = run["delivered"]["blocks"]
    checks = {
        **first,
        "accepted_equals_sent": sum(accepted.values()) == sum(sent),
        "pipeline_rows_in_equals_sent": rows_in == sent,
        "ingress_dropped_zero": not stats_end.get("ingress_dropped"),
        "overflow_empty": not stats_end.get("overflow"),
        "every_block_explained_by_a_prefix": not rep["failures"],
        "every_reading_frame_answered": not rep["unanswered"],
        "every_registration_held_at_the_end":
            rep["final_prefix"] <= rep["regs"].total,
        "one_room_type_code": len(rep["codes"]) <= 1,
        "no_expired_rows": not any(bool(b.is_expired.any()) for b in blocks),
    }
    fails = [f"conservation check {k} failed" for k, v in checks.items()
             if not v]
    fails += rep["failures"][:5]
    fails += [f"reading frame {f} has no block"
              for f in rep["unanswered"][:5]]
    measured = set(frames["frame"][frames["status"] == 200].tolist())
    unanswered_measured = sum(events.plan_of(f)["rows"]
                              for f in rep["unanswered"] if f in measured)
    rows_out = int(sum(b.count for b in blocks))
    return {
        "checks": checks,
        "conserved": all(checks.values()),
        "failures": fails,
        "attempted": attempted,
        "failed": refused + rep["bad_events"] + unanswered_measured,
        "detail": {"table": table, "sent": sent, "rows_in": rows_in,
                   "blocks": len(blocks), "rows_out": rows_out,
                   "registrations_applied": rep["final_prefix"],
                   "registration_share": sent[DEVICES] / max(1, sum(sent)),
                   "refused_events": refused},
    }


def verify_sample(run: dict, rng) -> dict:
    """>= 64 seeded blocks against the per-event `Registry`, fed every
    registration in order, in one sweep."""
    rep = replay(run)
    if rep["failures"]:
        return {"failures": [], "sampled": 0, "unit": "blocks"}
    events = run["events"]
    regs = rep["regs"]
    types = _types(run)
    blocks = run["delivered"]["blocks"]
    good = [b for b, p in enumerate(rep["prefix"]) if p is not None]
    picks = sorted(rng.choice(good, min(SAMPLE, len(good)),
                              replace=False).tolist()) if good else []
    picks.sort(key=lambda b: rep["prefix"][b])
    names = np.array(types, object)
    registry = Registry()
    fed = 0
    k = 0  # the registration frame being fed
    fails: list = []
    for b in picks:
        prefix = rep["prefix"][b]
        while fed < prefix:
            f = regs.frames[k]
            start = regs.start[f]
            cols = events.frame_columns(f)
            n = regs.rows[k]
            a, z = fed - start, min(n, prefix - start)
            ids, rooms, kinds = _as_lists(events, f, names)
            registry.upsert_many(
                ids[a:z], zip(rooms[a:z], kinds[a:z],
                              range(f * regs.stride + a, f * regs.stride + z)))
            fed = start + z
            if z == n:
                k += 1
        fails += _compare(run, blocks[b], rep["frame"][b], registry)
    return {"failures": fails, "sampled": len(picks), "unit": "blocks"}


def _as_lists(events, f: int, names) -> tuple:
    """A registration frame's ids, rooms and types as Python lists, made
    once per generated frame (the producer cycles a pool of eight)."""
    key = ("registry_lists", events.source(f))
    out = events.memo.get(key)
    if out is None:
        cols = events.frame_columns(f)
        out = events.memo[key] = (cols["deviceID"].tolist(),
                                  cols["roomNo"].tolist(),
                                  names[cols["type_code"]].tolist())
    return out


def _compare(run: dict, block, f: int, registry: Registry) -> list:
    events = run["events"]
    cols = events.frame_columns(f)
    n = events.plan_of(f)["rows"]
    temp32 = cols["temp"].astype(np.float32)
    want = [r for r in (registry.enrich(i, float(t), f * events.stride + j)
                        for j, (i, t) in enumerate(zip(
                            cols["deviceID"][:n].tolist(),
                            temp32[:n].tolist())))
            if r is not None]
    if block.count != len(want):
        return [f"frame {f}: {block.count} rows, the per-event reference "
                f"gives {len(want)}"]
    got = record.gather([(block, 0, block.count)], NUMERIC, ("roomType",))
    names = ("deviceID", "roomNo", "roomType", "temp", "timestamp",
             "regStamp")
    fails = []
    for c, name in enumerate(names):
        mine = got[name]
        theirs = [r[c] for r in want]
        if name == "temp":
            same = np.array_equal(
                mine.astype(np.float32).view(np.int32),
                np.array(theirs, np.float32).view(np.int32))
        elif name == "roomType":
            same = mine == theirs
        else:
            same = np.array_equal(mine, np.array(theirs, np.int64))
        if name == "timestamp":
            same = same and np.array_equal(got["ts"], mine)
        if not same:
            fails.append(f"frame {f}: column {name!r} differs from the "
                         "per-event reference")
    return fails
