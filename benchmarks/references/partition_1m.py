"""Plain reference for `partition_1m`: the SiddhiQL guide's value partition,

    partition with (deviceID of TempStream)
    begin
        from TempStream#window.length(10)
        select timestamp, roomNo, deviceID, max(temp) as maxTemp
        insert into DeviceTempStream;
    end;

"the maximum temperature recorded for the last 10 events emitted per
`deviceID`". Per event (`KeyedLengthWindows`: a dict of deques, no numpy,
nothing of the program): append `temp` to the device's `deque(maxlen=10)`,
emit `(timestamp, roomNo, deviceID, max(deque))`. One row per accepted
event, carrying its creation stamp, in arrival order.

**A device's window depends on everything that came before**, in the order
in which the engine serialised the producers' frames, and the rows say what
that was: every output row carries the stamp of the event it answers. The
delivered rows are cut into runs of consecutive stamps inside one frame (a
frame is one batch at these sizes, so a block is one run), and the replay
takes the runs in that order. It is numpy over runs — a `[devices, 10]`
array of the windows' values and a count a device — with the devices that
come more than once in a run taken in rounds by their rank among the run's
events of that device (`Plan`). The reference keeps its state by the
generator's device rank; that the row names the right device is checked
against the generator's own id and room of that rank.

- `account(run)`: the engine's drop counter first (a run that turned a key
  away ends there); conservation by `checks.OneToOne` (every accepted event
  answered by exactly one row, each producer's frames in order, nothing
  expired delivered); the `timestamp` column equal to the row's stamp and,
  within a block, rising (arrival order).
- `verify_sample(run, rng)`: EVERY delivered run replayed in order, and
  every row of >= 64 seeded runs held to it: `deviceID` and `roomNo` the
  generator's, `maxTemp` bit for bit. The vectorised replay is itself held
  to `KeyedLengthWindows` in benchmarks/tests/test_partition.py.
- `completed(run, lo, hi)`: an event counts when its row is delivered in the
  span (`checks.OneToOne`).
"""

from __future__ import annotations

from collections import deque

import numpy as np

import checks

SAMPLE = 64  # runs checked row by row, at least (or all there are)
LENGTH = 10
DROP_COUNTER = "partition_keys_dropped"


# ------------------------------------------------- the per-event reference


class KeyedLengthWindows:
    """One event per turn; `arrive` returns the row's maximum."""

    def __init__(self, length: int = LENGTH) -> None:
        self.length = length
        self.windows: dict = {}

    def arrive(self, key, value):
        window = self.windows.get(key)
        if window is None:
            window = self.windows[key] = deque(maxlen=self.length)
        window.append(value)
        return max(window)


# ------------------------------------------------------ the run-wise replay


class Plan:
    """What the replay needs of a run's devices, worked out once a
    generated frame: per event how many events of its device come before it
    in the run (its round); the events in (device, position) order; the
    run's distinct devices and how often each comes."""

    def __init__(self, devices: np.ndarray) -> None:
        order = np.argsort(devices, kind="stable")
        s_dev = devices[order]
        first = np.r_[True, s_dev[1:] != s_dev[:-1]]
        start = np.maximum.accumulate(
            np.where(first, np.arange(s_dev.size), 0))
        self.order = order
        self.sorted_devices = s_dev
        self.sorted_rounds = np.arange(s_dev.size) - start
        self.rounds = np.empty(devices.size, np.int64)
        self.rounds[order] = self.sorted_rounds
        self.distinct = s_dev[first]
        self.times = np.diff(np.r_[np.nonzero(first)[0], s_dev.size])


class Replay:
    """The same rule over runs. `values[d]` is device d's ring, written at
    `count[d] % length`; its window is the ring's first `min(count, length)`
    places (a maximum does not ask for their order)."""

    def __init__(self, devices: int, length: int = LENGTH) -> None:
        self.length = length
        self.values = np.zeros((devices, length), np.float32)
        self.count = np.zeros(devices, np.int64)

    def skip(self, devices, temps, plan=None) -> None:
        """Take a run without giving its rows: every event written in
        (device, position) order, so that where a device has more than
        `length` events the later ones land last."""
        plan = plan or Plan(devices)
        d = plan.sorted_devices
        self.values[d, (self.count[d] + plan.sorted_rounds) % self.length] \
            = temps[plan.order]
        self.count[plan.distinct] += plan.times

    def run(self, devices, temps, plan=None) -> np.ndarray:
        """Take a run; returns every row's maximum."""
        rounds = (plan or Plan(devices)).rounds
        out = np.empty(devices.size, np.float32)
        places = np.arange(self.length)
        for r in range(int(rounds.max()) + 1 if rounds.size else 0):
            sel = np.nonzero(rounds == r)[0]
            d = devices[sel]  # distinct: one event a device a round
            self.values[d, self.count[d] % self.length] = temps[sel]
            self.count[d] += 1
            held = places[None, :] < np.minimum(self.count[d],
                                                self.length)[:, None]
            out[sel] = np.where(held, self.values[d], -np.inf).max(axis=1)
        return out


# ------------------------------------------------------- what run.py asks


def passes(cols: dict, config: dict, stream: str) -> np.ndarray:
    return np.ones(cols["device"].size, bool)


def expected_rows(passed: int, config: dict) -> int:
    return passed


_FAMILY = checks.OneToOne(passes, expected_rows)
completed = _FAMILY.completed
expected_output_rows = _FAMILY.expected_output_rows


def _dropped(run: dict) -> dict:
    """What the engine says it turned away, by either of its accounts."""
    stats = run["stats_end"]
    lost = {k: v for k, v in (stats.get("overflow") or {}).items()
            if k.rsplit(".", 1)[-1] == DROP_COUNTER}
    for name, p in (stats.get("partitions") or {}).items():
        if p.get("keys_dropped"):
            lost[f"partitions.{name}.keys_dropped"] = p["keys_dropped"]
    return lost


def runs_of(run: dict) -> list:
    """The serialisation that happened: the delivered rows, in order, as
    (block, first row, rows, first stamp) runs of consecutive stamps inside
    one frame."""
    stride = run["events"].stride
    out = []
    for b, block in enumerate(run["delivered"]["blocks"]):
        ts = block.timestamps
        if not ts.size:
            continue
        cut = np.nonzero((np.diff(ts) != 1) | (ts[1:] % stride == 0))[0] + 1
        edges = np.r_[0, cut, ts.size]
        out.extend((b, int(a), int(z - a), int(ts[a]))
                   for a, z in zip(edges[:-1], edges[1:]))
    return out


def _events_of(run: dict, start: int, n: int):
    """(frame columns, row slice, (the run's replay plan, its temperatures
    as float32)) — remembered per generated frame: the producers cycle
    their pools, so a window's thousands of frames are some tens of
    distinct ones."""
    events = run["events"]
    f, row = divmod(start, events.stride)
    cols = events.frame_columns(f)
    key = ("partition_plan", events.source(f), row, n)
    plan = events.memo.get(key)
    if plan is None:
        plan = events.memo[key] = (
            Plan(cols["device"][row:row + n]),
            cols["temp"][row:row + n].astype(np.float32))
    return cols, slice(row, row + n), plan


def account(run: dict) -> dict:
    lost = _dropped(run)
    if lost:
        # nothing further is worth the time: devices were turned away
        frames = run["frames"]
        return {"checks": {"no_key_turned_away": False},
                "conserved": False,
                "failures": [f"the partition dropped events of keys that "
                             f"found no slot: {lost}"],
                "attempted": int(frames["rows"].sum()),
                "failed": int(frames["rows"].sum()),
                "detail": {"overflow": lost}}
    out = _FAMILY.account(run)
    stride = run["events"].stride
    wrong_stamp = disordered = runs = 0
    for block in run["delivered"]["blocks"]:
        ts = block.timestamps
        if not np.array_equal(block.column("timestamp"), ts):
            wrong_stamp += 1
        if not ts.size:
            continue
        step = np.diff(ts)
        same_frame = ts[1:] // stride == ts[:-1] // stride
        runs += 1 + int(np.count_nonzero((step != 1) | ~same_frame))
        # arrival order: within a frame the rows' stamps rise
        if np.any((step <= 0) & same_frame):
            disordered += 1
    checks_ = {"no_key_turned_away": True,
               "timestamp_column_is_the_rows_stamp": wrong_stamp == 0,
               "rows_in_arrival_order_within_a_frame": disordered == 0}
    out["checks"].update(checks_)
    out["conserved"] = out["conserved"] and all(checks_.values())
    out["failures"] += [f"check {k} failed" for k, v in checks_.items()
                        if not v]
    out["failed"] += wrong_stamp + disordered
    partitions = run["stats_end"].get("partitions") or {}
    out["detail"].update({
        "runs": runs,
        "keys_held": {n: p.get("keys") for n, p in partitions.items()}})
    return out


def verify_sample(run: dict, rng) -> dict:
    if _dropped(run):
        return {"failures": [], "sampled": 0, "unit": "runs"}
    runs = runs_of(run)
    picks = set(rng.choice(len(runs), min(SAMPLE, len(runs)),
                           replace=False).tolist()) if runs else set()
    blocks = run["delivered"]["blocks"]
    devices = max(plan["params"]["keys"] for plan in run["events"].plans)
    replay = Replay(devices, LENGTH)
    fails: list = []
    for i, (b, a, n, start) in enumerate(runs):
        cols, rows, (plan, temps) = _events_of(run, start, n)
        device = cols["device"][rows]
        if i not in picks:
            replay.skip(device, temps, plan)
            continue
        want = replay.run(device, temps, plan)
        block = blocks[b]
        got = {name: block.column(name)[a:a + n]
               for name in ("deviceID", "roomNo", "maxTemp")}
        same = {
            "deviceID": np.array_equal(got["deviceID"],
                                       cols["deviceID"][rows]),
            "roomNo": np.array_equal(got["roomNo"], cols["roomNo"][rows]),
            "maxTemp": np.array_equal(
                got["maxTemp"].astype(np.float32).view(np.int32),
                want.view(np.int32)),
        }
        for name, ok in same.items():
            if not ok:
                fails.append(f"run at stamp {start}: column {name!r} "
                             "differs from the reference")
        if not same["maxTemp"]:
            bad = np.nonzero(got["maxTemp"].astype(np.float32) != want)[0]
            at = int(bad[0])
            fails.append(
                f"run at stamp {start}: {bad.size} of {n} maxima differ, "
                f"first at row {at}: {float(got['maxTemp'][at])}, not "
                f"{float(want[at])}")
    return {"failures": fails, "sampled": len(picks), "unit": "runs"}
