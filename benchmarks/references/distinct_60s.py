"""Plain reference for `distinct_60s`: a sliding time window's distinct
count,

    from cseEventStream#window.time(60000 sec)
    select timestamp, distinctCount(symbol) as distinctSymbols
    insert into distinctStream;

under @app:playback, the harness's clock ticking once an event (a stamp is
the event's global index), so the window is `W` = 60,000,000 ticks wide.

Per event (`DistinctTimeWindow`: a deque, a Counter, no numpy, nothing of
the program), upstream's TimeWindowProcessor rule: the clock moves to the
running maximum of the stamps; the FIFO's head is popped while `head.stamp +
W <= clock` and the walk stops at the first head that is not due, whatever
lies behind it; the arrival is appended; the row carries `len(counter)`.

**The result depends on the order in which the engine serialised the
producers' frames** (each closed-loop producer stamps its own frame counter,
so frames arrive with stamps out of order), and the rows say what it was:
every output row carries the stamp of the event it answers, in the order the
engine took the events. The delivered rows are cut into runs of consecutive
stamps inside one frame (a frame is one batch at these sizes, so a block is
one run); the replay takes the runs in that order and keeps the FIFO as runs
too, never as 140 M tuples: inside a run stamps rise by one, so the running
maximum of the stamps up to a row is `max(what stood before the run, the
row's stamp)` and the rows that are due form a prefix found by arithmetic.

- `account(run)`: the engine's two loss counters first (a run that lost rows
  ends there: the parent's, whose ring holds 0.2 % of the window);
  conservation by `checks.OneToOne` (every accepted event answered by
  exactly one row, each producer's frames in order, nothing expired
  delivered); the `timestamp` column equal to the row's stamp; and over
  EVERY run delivered, its last row equal to the replay's distinct count at
  the run's end (two `bincount`s and a `count_nonzero` a run).
- `verify_sample(run, rng)`: >= 64 seeded runs, every row, in order, exact,
  from the replay's state at the run's start: the run's expiries and
  arrivals as events sorted by symbol then position, a segmented running
  count, the 0->1 and 1->0 transitions summed in position order. The
  vectorised form is itself held to `DistinctTimeWindow` in
  benchmarks/tests/test_distinct.py.
- `completed(run, lo, hi)`: an event counts when its row is delivered in the
  span (`checks.OneToOne`).
"""

from __future__ import annotations

from collections import Counter, deque

import numpy as np

import checks

SAMPLE = 64  # runs checked row by row, at least (or all there are)
LOSS_COUNTERS = ("window_ring_overflow", "window_expiry_deferred")


# ------------------------------------------------- the per-event reference


class DistinctTimeWindow:
    """One event per turn; `arrive` returns the row's distinct count."""

    def __init__(self, width: int) -> None:
        self.width = width
        self.clock = None
        self.fifo: deque = deque()
        self.counts: Counter = Counter()

    def arrive(self, symbol, stamp: int) -> int:
        self.clock = stamp if self.clock is None else max(self.clock, stamp)
        while self.fifo and self.fifo[0][1] + self.width <= self.clock:
            gone, _ = self.fifo.popleft()
            self.counts[gone] -= 1
            if not self.counts[gone]:
                del self.counts[gone]
        self.fifo.append((symbol, stamp))
        self.counts[symbol] += 1
        return len(self.counts)


# ------------------------------------------------------ the run-wise replay


class Replay:
    """The same rule over runs of consecutive stamps. A run in the FIFO is
    [first stamp, rows, symbol ids, running maximum of all stamps before
    it]; `head` rows of the first run are already gone."""

    def __init__(self, width: int, keys: int) -> None:
        self.width = width
        self.clock = -1
        self.peak = -1  # running maximum of every stamp appended so far
        self.fifo: deque = deque()
        self.head = 0
        self.rows = 0  # rows in the window
        self.counts = np.zeros(keys, np.int64)

    def _due(self, limit: int):
        """The FIFO's rows due at the clock, at most `limit`, oldest first,
        as (run, first row, rows) pieces: those whose running-maximum stamp
        is <= clock - width, a prefix."""
        x = self.clock - self.width
        left, first = limit, self.head
        for run in self.fifo:
            start, n, _, before = run
            due = 0 if before > x else int(np.clip(x - start + 1, 0, n))
            take = min(due - first, left)
            if take <= 0:
                return
            yield run, first, take
            left -= take
            if first + take < n:
                return
            first = 0

    def _drop(self, gone: int) -> None:
        self.rows -= gone
        gone += self.head
        while self.fifo and gone >= self.fifo[0][1]:
            gone -= self.fifo.popleft()[1]
        self.head = gone

    def run(self, start: int, symbols: np.ndarray, rows: bool = False):
        """Take one run of arrivals stamped start, start + 1, ...; returns
        the distinct count of its last row, or of every row with `rows`."""
        n = symbols.size
        clock0 = self.clock
        self.fifo.append((start, n, symbols, self.peak))
        self.peak = max(self.peak, start + n - 1)
        self.clock = max(clock0, start + n - 1)
        # the last arrival's pops come before its own append: every row but
        # itself may go
        pieces = list(self._due(self.rows + n - 1))
        every = self._rows(start, symbols, clock0, pieces) if rows else None
        gone = 0
        for (_, _, syms, _), first, take in pieces:
            self.counts -= np.bincount(syms[first:first + take],
                                       minlength=self.counts.size)
            gone += take
        self.counts += np.bincount(symbols, minlength=self.counts.size)
        self.rows += n
        self._drop(gone)
        return every if rows else int(np.count_nonzero(self.counts))

    def _rows(self, start, symbols, clock0, pieces) -> np.ndarray:
        """Every row's count, from the state at the run's start (called
        before it changes). Arrival i's clock is max(clock0, start + i); a
        due row whose running-maximum stamp is g goes at the first arrival
        whose clock reaches g + width, a row of this run not before the
        arrival after its own."""
        n = symbols.size
        syms, at = [], []
        own = self.rows  # FIFO position of the run's first row
        seen = 0
        for (first_stamp, _, run_syms, before), first, take in pieces:
            g = np.maximum(before, first_stamp + np.arange(first,
                                                           first + take))
            due = g + self.width
            when = np.where(due <= clock0, 0, np.maximum(due - start, 0))
            position = seen + np.arange(take)
            when = np.maximum(when, position - own + 1)  # after it arrived
            syms.append(run_syms[first:first + take])
            at.append(when)
            seen += take
        out_sym = np.concatenate(syms) if syms else np.zeros(0, np.int64)
        out_at = np.concatenate(at) if at else np.zeros(0, np.int64)
        # events in processing order: at arrival i the rows that go, oldest
        # first, then the arrival
        sym = np.concatenate([out_sym, symbols])
        key = np.concatenate([2 * out_at, 2 * np.arange(n) + 1])
        delta = np.concatenate([-np.ones(out_sym.size, np.int64),
                                np.ones(n, np.int64)])
        order = np.lexsort((np.arange(sym.size), key, sym))
        s_sym, s_delta = sym[order], delta[order]
        running = np.cumsum(s_delta)
        first_of = np.r_[True, s_sym[1:] != s_sym[:-1]]
        opens = np.maximum.accumulate(
            np.where(first_of, np.arange(sym.size), 0))
        count = self.counts[s_sym] + running - (running - s_delta)[opens]
        change = np.zeros(sym.size, np.int64)
        change[order] = np.where(s_delta > 0, count == 1,
                                 -(count == 0).astype(np.int64))
        in_time = np.argsort(key, kind="stable")
        distinct = int(np.count_nonzero(self.counts)) \
            + np.cumsum(change[in_time])
        return distinct[key[in_time] % 2 == 1]


# ------------------------------------------------------- what run.py asks


def passes(cols: dict, config: dict, stream: str) -> np.ndarray:
    return np.ones(cols["symbol"].size, bool)


def expected_rows(passed: int, config: dict) -> int:
    return passed


_FAMILY = checks.OneToOne(passes, expected_rows)
completed = _FAMILY.completed
expected_output_rows = _FAMILY.expected_output_rows


def _width(run: dict) -> int:
    return int(run["config"]["sizes"]["window_sec"]) * 1000


def _keys(run: dict) -> int:
    return max(plan["params"]["keys"] for plan in run["events"].plans)


def _lost(run: dict) -> dict:
    overflow = run["stats_end"].get("overflow") or {}
    return {k: v for k, v in overflow.items()
            if k.rsplit(".", 1)[-1] in LOSS_COUNTERS}


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


def _symbols(run: dict, start: int, n: int) -> np.ndarray:
    events = run["events"]
    f, row = divmod(start, events.stride)
    return events.frame_columns(f)["symbol"][row:row + n]


def account(run: dict) -> dict:
    lost = _lost(run)
    if lost:
        # nothing further is worth the time: the window lost rows
        frames = run["frames"]
        return {"checks": {"window_lost_no_rows": False},
                "conserved": False,
                "failures": [f"the window lost rows: {lost}"],
                "attempted": int(frames["rows"].sum()),
                "failed": int(frames["rows"].sum()),
                "detail": {"overflow": lost}}
    out = _FAMILY.account(run)
    blocks = run["delivered"]["blocks"]
    replay = Replay(_width(run), _keys(run))
    wrong_last = wrong_stamp = 0
    distinct_hwm = rows_hwm = 0
    first_wrong = None
    runs = runs_of(run)
    for b, a, n, start in runs:
        want = replay.run(start, _symbols(run, start, n))
        got = int(blocks[b].column("distinctSymbols")[a + n - 1])
        if got != want:
            wrong_last += 1
            first_wrong = first_wrong or (start, got, want)
        distinct_hwm = max(distinct_hwm, want)
        rows_hwm = max(rows_hwm, replay.rows)
    for block in blocks:
        if not np.array_equal(block.column("timestamp"), block.timestamps):
            wrong_stamp += 1
    checks_ = {"every_run_ends_on_the_replays_count": wrong_last == 0,
               "timestamp_column_is_the_rows_stamp": wrong_stamp == 0}
    out["checks"].update(checks_)
    out["conserved"] = out["conserved"] and all(checks_.values())
    out["failures"] += [f"check {k} failed" for k, v in checks_.items()
                        if not v]
    if first_wrong:
        out["failures"].append(
            "the run at stamp %d ends on %d, the replay on %d" % first_wrong)
    out["failed"] += wrong_last
    out["detail"].update({
        "runs": len(runs), "runs_ending_wrong": wrong_last,
        "window_rows_hwm": rows_hwm, "distinct_hwm": distinct_hwm,
        "distinct_at_end": int(np.count_nonzero(replay.counts)),
        "fifo_runs_at_end": len(replay.fifo)})
    return out


def verify_sample(run: dict, rng) -> dict:
    if _lost(run):
        return {"failures": [], "sampled": 0, "unit": "runs"}
    runs = runs_of(run)
    picks = set(rng.choice(len(runs), min(SAMPLE, len(runs)),
                           replace=False).tolist()) if runs else set()
    blocks = run["delivered"]["blocks"]
    replay = Replay(_width(run), _keys(run))
    fails: list = []
    for i, (b, a, n, start) in enumerate(runs):
        symbols = _symbols(run, start, n)
        if i not in picks:
            replay.run(start, symbols)
            continue
        want = replay.run(start, symbols, rows=True)
        got = blocks[b].column("distinctSymbols")[a:a + n]
        if not np.array_equal(got, want):
            at = int(np.nonzero(got != want)[0][0])
            fails.append(
                f"run at stamp {start}: {int((got != want).sum())} of {n} "
                f"rows differ from the reference, first at row {at}: "
                f"{int(got[at])}, not {int(want[at])}")
    return {"failures": fails, "sampled": len(picks), "unit": "runs"}
