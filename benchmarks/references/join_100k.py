"""Plain reference for `join_100k`: the stream-stream windowed join,

    from cseEventStream#window.length(W) as t
    join quoteEventStream#window.length(W) as q on t.symbol == q.symbol
    select t.symbol as symbol, t.price as tradePrice, q.price as quotePrice,
           t.timestamp as tradeStamp, q.timestamp as quoteStamp
    insert into joinedStream;

Per event (`WindowedJoin`, no numpy, nothing of the program): an arriving
event looks up the opposite side's live rows with its symbol, gives one row
per match, oldest match first (upstream's `find()` order), and is then
appended to its own side's window, which evicts its oldest row once it holds
more than W.

**The result depends on how the engine serialised the two streams' frames**,
so `checks.OneToOne` does not fit and this file answers run.py's four
questions itself. The engine runs a frame (= one micro-batch) as a whole:
its events probe the opposite window as it stood before the frame, never
their own side. The serialisation is read from the callback's log: blocks
arrive in step order, a block's timestamps are its trigger events' global
indexes, so each block names its trigger frame; `tradeStamp`/`quoteStamp`
name both input events of every row. The parent's warm-up frames lead, in
number order (run.py posts them stream by stream and drains in between). An
accepted frame with no block stands right before its producer's next
answered frame and may stand only where the reference too yields no row.

- `account(run)`: over EVERYTHING delivered, vectorised. Every accepted
  frame answered by at most one block and every block by one accepted frame;
  a block's rows are its frame's probes against the opposite window's W
  newest events at that point of the serialisation: as many as the reference
  counts (a `bincount` of the window's keys), every pair a true match (both
  keys equal) whose build row was live (arrived, not expired), and strictly
  rising by (probe event, build row's age): no pair twice, a probe's matches
  oldest first. Count + truth + distinctness make the block's pairs exactly
  the reference's. The engine's own drop counter must read 0.
- `verify_sample(run, rng)`: >= 64 seeded blocks, row for row, in order,
  all five columns and the timestamp bit for bit against the per-event loop.
- `completed(run, lo, hi)`: input events of both streams whose block reached
  the callback in the span.
"""

from __future__ import annotations

from collections import deque

import numpy as np

import record

LEFT, RIGHT = 0, 1  # the join's sides, in the configuration's input order
SAMPLE = 64  # blocks checked row by row, at least (or all there are)
NUMERIC = ("tradePrice", "quotePrice", "tradeStamp", "quoteStamp")
DROP_COUNTER = "join_pairs_dropped"


# ------------------------------------------------- the per-event reference


class WindowedJoin:
    """Both windows of one join. An event is (key, payload); a pair is
    (left payload, right payload) whichever side triggered."""

    def __init__(self, window: int) -> None:
        self.window = window
        self._rows = (deque(), deque())  # per side: keys in arrival order
        self._by_key = ({}, {})  # per side: key -> deque of payloads

    def matches(self, side: int, key, payload) -> list:
        """The pairs an event arriving on `side` gives: the opposite side's
        live rows with its key, oldest first."""
        found = self._by_key[1 - side].get(key)
        if found is None:
            return []
        if side == LEFT:
            return [(payload, other) for other in found]
        return [(other, payload) for other in found]

    def append(self, side: int, key, payload) -> None:
        """The event joins its own side's window, which evicts its oldest
        row once it holds more than W."""
        rows, by_key = self._rows[side], self._by_key[side]
        rows.append(key)
        mine = by_key.get(key)
        if mine is None:
            by_key[key] = deque((payload,))
        else:
            mine.append(payload)
        if len(rows) > self.window:
            old = rows.popleft()
            q = by_key[old]
            q.popleft()
            if not q:
                del by_key[old]

    def arrive(self, side: int, key, payload) -> list:
        pairs = self.matches(side, key, payload)
        self.append(side, key, payload)
        return pairs

    def frame(self, side: int, keys, payloads) -> list:
        """One frame's pairs: by probe event, a probe's matches oldest
        first. The frame's events never meet each other."""
        out = []
        for key, payload in zip(keys, payloads):
            out.extend(self.arrive(side, key, payload))
        return out


# ------------------------------------------------------- the serialisation


def _window(run: dict) -> int:
    return int(run["config"]["sizes"]["window"])


def _accepted(run: dict) -> dict:
    """frame number -> rows the server accepted, for every frame that got a
    `200` (the producers') or was posted by the parent itself."""
    frames = run["frames"]
    ok = frames["status"] == 200
    out = dict(run["sent_extra"])
    out.update(zip(frames["frame"][ok].tolist(),
                   frames["accepted"][ok].tolist()))
    return out


def _block_frames(run: dict) -> np.ndarray:
    """Trigger frame of every delivered block, -1 where a block's rows do
    not all belong to one frame."""
    cached = run.get("join_block_frames")
    if cached is None:
        stride = run["events"].stride
        cached = run["join_block_frames"] = np.array(
            [int(b.timestamps[0]) // stride
             if b.count and int(b.timestamps.min()) // stride
             == int(b.timestamps.max()) // stride else -1
             for b in run["delivered"]["blocks"]], np.int64)
    return cached


def serialisation(run: dict) -> dict:
    """`order`: every accepted frame in the order the engine ran them;
    `block_of`: frame -> index of the block that answers it; `failures`: in
    words, where the log cannot be a serialisation of what was accepted."""
    cached = run.get("join_serialisation")
    if cached is not None:
        return cached
    events = run["events"]
    accepted = _accepted(run)
    answered = _block_frames(run).tolist()
    fails: list = []
    block_of: dict = {}
    for b, f in enumerate(answered):
        if f not in accepted:
            fails.append(f"block {b} answers no single accepted frame ({f})")
        elif f in block_of:
            fails.append(f"frame {f} is answered by blocks {block_of[f]} "
                         f"and {b}")
        else:
            block_of[f] = b
    producer_of = {f: events.source(f)[:2] for f in accepted}
    warm = sorted(f for f in accepted if f < events.warm_total)
    order = list(warm)
    warm_answered = [f for f in warm if f in block_of]
    first = [f for f in answered if f in block_of][:len(warm_answered)]
    if first != warm_answered:
        fails.append(f"the warm-up frames' blocks {warm_answered} do not "
                     f"lead the log in that order: {first}")
    # each producer's accepted frames, oldest first, not yet placed
    waiting: dict = {}
    for f in sorted(accepted):
        if f >= events.warm_total:
            waiting.setdefault(producer_of[f], deque()).append(f)
    for b, f in enumerate(answered):
        if block_of.get(f) != b or f < events.warm_total:
            continue
        mine = waiting[producer_of[f]]
        if f not in mine:
            continue  # placed already: its producer's order broke below
        while mine[0] != f:
            # a frame with no block, or this producer's frames out of order
            skipped = mine.popleft()
            if skipped in block_of:
                fails.append(f"frame {skipped} of producer "
                             f"{producer_of[f]} ran after its frame {f}")
            order.append(skipped)
        order.append(mine.popleft())
    for mine in waiting.values():
        order.extend(mine)  # never answered: they stand last
    cached = run["join_serialisation"] = {
        "order": order, "block_of": block_of, "failures": fails}
    return cached


class Replay:
    """Walks a serialisation and says, for each frame as it runs, what the
    opposite side's window holds: the W newest events of that side, as
    pieces `(frame, first row, last row + 1)`, oldest first."""

    def __init__(self, run: dict) -> None:
        self.events = run["events"]
        self.window = _window(run)
        self.tails = ([], [])  # per side: pieces that may still be live

    def side(self, f: int) -> int:
        return self.events.source(f)[0]

    def rows(self, f: int) -> int:
        return self.events.plan_of(f)["rows"]

    def opposite(self, f: int) -> list:
        """The window frame `f` probes, before `f` itself is appended."""
        return list(self.tails[1 - self.side(f)])

    def append(self, f: int) -> None:
        tail = self.tails[self.side(f)]
        tail.append((f, 0, self.rows(f)))
        held = sum(z - a for _, a, z in tail)
        while held > self.window:  # evict the oldest rows beyond W
            g, a, z = tail[0]
            cut = min(held - self.window, z - a)
            if cut == z - a:
                tail.pop(0)
            else:
                tail[0] = (g, a + cut, z)
            held -= cut

    def keys(self, pieces: list) -> np.ndarray:
        if not pieces:
            return np.zeros(0, np.int64)
        return np.concatenate([self.events.frame_columns(g)["symbol"][a:z]
                               for g, a, z in pieces])


# ------------------------------------------------------- run.py's questions


def expected_output_rows(run: dict, sent_frames) -> int:
    """A lower bound only: how many rows a join owes depends on the
    serialisation, which nobody knows beforehand. `rt.drain()` returns after
    the last callback has (the program's repair of PR 26), so nothing is
    left to wait for; one row at least is owed per accepted frame that met a
    filled window. The frames' columns are regenerated here, which the
    account needs anyway."""
    events = run["events"]
    for f in sent_frames:
        events.frame_columns(f)
    return max(0, len(sent_frames) - events.warm)


def completed(run: dict, lo_ns: int, hi_ns: int) -> float:
    """Input events (of both streams) whose results reached the callback in
    [lo, hi): each measured frame is credited its rows times the share of
    its output rows delivered then."""
    frames, delivered = run["frames"], run["delivered"]
    answered = _block_frames(run)
    if not frames["frame"].size or not answered.size:
        return 0.0
    n_frames = int(max(frames["frame"].max(), answered.max())) + 1
    known = answered >= 0
    t = delivered["enter_ns"]
    inside = known & (t >= lo_ns) & (t < hi_ns)
    total = np.bincount(answered[known], weights=delivered["rows"][known],
                        minlength=n_frames)
    then = np.bincount(answered[inside], weights=delivered["rows"][inside],
                       minlength=n_frames)
    ok = frames["status"] == 200
    f = frames["frame"][ok]
    share = np.divide(then[f], total[f], out=np.zeros(f.size),
                      where=total[f] > 0)
    return float((frames["rows"][ok] * share).sum())


def account(run: dict) -> dict:
    events, frames = run["events"], run["frames"]
    stats_end = run["stats_end"]
    stride = events.stride
    blocks = run["delivered"]["blocks"]
    ser = serialisation(run)
    order, block_of = ser["order"], ser["block_of"]
    accepted = _accepted(run)
    replay = Replay(run)
    window = replay.window

    sent_by_stream = [0] * len(events.plans)
    expected = pairs_out = missing = spurious = repeated = 0
    unanswered: list = []
    short: list = []
    misordered = 0
    bad_events = np.zeros(0, bool)
    failed_measured = 0
    measured = set(frames["frame"][frames["status"] == 200].tolist())
    for f in order:
        side, n = replay.side(f), replay.rows(f)
        sent_by_stream[side] += n
        pieces = replay.opposite(f)
        probe_keys = events.frame_columns(f)["symbol"]
        window_keys = replay.keys(pieces)
        per_probe = np.bincount(
            window_keys, minlength=int(probe_keys.max()) + 1)[probe_keys] \
            if window_keys.size else np.zeros(n, np.int64)
        want = int(per_probe.sum())
        expected += want
        b = block_of.get(f)
        if b is None:
            if want:
                unanswered.append(f)
            missing += want
            bad_events = per_probe > 0
        else:
            blk = blocks[b]
            pairs_out += blk.count
            stamps = (blk.column("tradeStamp"), blk.column("quoteStamp"))
            probe_row = stamps[side] - f * stride
            build = stamps[1 - side]
            # where in the opposite window (0 = its oldest row) each build
            # row lies; -1: expired by then, or not yet arrived
            age_rank = np.full(blk.count, -1, np.int64)
            base = 0
            for g, a, z in pieces:
                row = build - g * stride
                here = (row >= a) & (row < z)
                age_rank[here] = base + row[here] - a
                base += z - a
            true = (stamps[side] == blk.timestamps) & (probe_row >= 0) \
                & (probe_row < n) & (age_rank >= 0)
            probe_row = np.clip(probe_row, 0, n - 1)
            if window_keys.size:
                true &= probe_keys[probe_row] \
                    == window_keys[np.maximum(age_rank, 0)]
            rank = probe_row * (window + 1) + age_rank
            falling = np.nonzero(np.diff(rank) <= 0)[0] + 1
            n_true = int(np.count_nonzero(true))
            repeated += n_true - int(np.unique(rank[true]).size)
            spurious += blk.count - n_true
            misordered += int(falling.size)
            got = np.bincount(probe_row[true], minlength=n)
            missing += int(np.maximum(per_probe - got, 0).sum())
            if blk.count != want:
                short.append((f, blk.count, want))
            bad_events = got != per_probe
            bad_events[probe_row[~true]] = True
            bad_events[probe_row[falling]] = True
        if f in measured:
            failed_measured += int(np.count_nonzero(bad_events))
        replay.append(f)

    drops = {k: v for k, v in (stats_end.get("overflow") or {}).items()
             if k.endswith(DROP_COUNTER)}
    pipes = stats_end.get("ingress_pipeline") or {}
    rows_in = [(pipes.get(plan["stream"]) or {}).get("rows_in")
               for plan in events.plans]
    sent_rows = sum(sent_by_stream)
    checks = {
        "accepted_equals_sent": sum(accepted.values()) == sent_rows,
        "pipeline_rows_in_equals_sent": rows_in == sent_by_stream,
        "ingress_dropped_zero": not stats_end.get("ingress_dropped"),
        "log_is_a_serialisation": not ser["failures"],
        "every_frame_answered": not unanswered,
        "rows_out_equal_the_reference_count":
            not short and pairs_out == expected,
        "only_true_pairs_of_live_rows": spurious == 0,
        "no_pair_twice": repeated == 0,
        "by_probe_then_oldest_match_first": misordered == 0,
        "no_pair_missing": missing == 0,
        "join_pairs_dropped_zero": not drops,
        "no_expired_rows": not any(bool(b.is_expired.any())
                                   for b in blocks),
    }
    fails = [f"conservation check {k} failed"
             for k, v in checks.items() if not v]
    fails += ser["failures"][:5]
    fails += [f"frame {f} has no block, the reference has rows for it"
              for f in unanswered[:5]]
    fails += [f"frame {f}: {got} rows, the reference counts {want}"
              for f, got, want in short[:5]]
    fails += [f"the engine dropped pairs: {drops}"] if drops else []
    refused = int(frames["rows"][frames["status"] != 200].sum())
    return {
        "checks": checks,
        "conserved": all(checks.values()),
        "failures": fails,
        "attempted": int(frames["rows"].sum()),
        "failed": refused + failed_measured,
        "detail": {"sent_rows": sent_rows,
                   "accepted": int(sum(accepted.values())),
                   "rows_in": rows_in, "frames": len(order),
                   "blocks": len(blocks), "pairs_expected": expected,
                   "rows_out": pairs_out, "missing": missing,
                   "spurious": spurious, "duplicates": repeated,
                   "misordered": misordered, "dropped": drops,
                   "refused_events": refused},
    }


def verify_sample(run: dict, rng) -> dict:
    blocks = run["delivered"]["blocks"]
    ser = serialisation(run)
    order, block_of = ser["order"], ser["block_of"]
    answered = [f for f in order if f in block_of]
    picks = set(rng.choice(answered, min(SAMPLE, len(answered)),
                           replace=False).tolist()) if answered else set()
    replay = Replay(run)
    fails: list = []
    for f in order:
        if f in picks:
            fails += _compare(run, f, blocks[block_of[f]], replay)
        replay.append(f)
    return {"failures": fails, "sampled": len(picks), "unit": "blocks"}


def _compare(run: dict, f: int, blk, replay: Replay) -> list:
    """One block against the per-event loop: the opposite window's events
    arrive in their order at a join of its own, then the frame's."""
    events = run["events"]
    stride = events.stride
    side = replay.side(f)
    join = WindowedJoin(replay.window)
    # the join is this block's alone: the window's events need not probe
    # (their pairs are other blocks') nor the frame's be appended
    for g, a, z in replay.opposite(f):
        keys = events.frame_columns(g)["symbol"][a:z].tolist()
        for key, index in zip(keys, range(g * stride + a, g * stride + z)):
            join.append(1 - side, key, index)
    cols = events.frame_columns(f)
    n = replay.rows(f)
    pairs = []
    for key, index in zip(cols["symbol"].tolist(),
                          range(f * stride, f * stride + n)):
        pairs.extend(join.matches(side, key, index))
    trade = np.fromiter((p[LEFT] for p in pairs), np.int64, len(pairs))
    quote = np.fromiter((p[RIGHT] for p in pairs), np.int64, len(pairs))
    if blk.count != len(pairs):
        return [f"frame {f}: {blk.count} rows, the per-event reference "
                f"gives {len(pairs)}"]
    want = {
        "event timestamp": (trade, quote)[side],
        "tradeStamp": trade, "quoteStamp": quote,
        "tradePrice": events.lookup(trade, ("price",))["price"]
        .astype(np.float32).view(np.int32),
        "quotePrice": events.lookup(quote, ("price",))["price"]
        .astype(np.float32).view(np.int32),
    }
    got = record.gather([(blk, 0, blk.count)], NUMERIC, ("symbol",))
    same = {
        "event timestamp": np.array_equal(got["ts"], want["event timestamp"]),
        "symbol": got["symbol"] == events.gens[LEFT].symbol_strings(
            events.lookup(trade, ("symbol",))["symbol"],
            events.plans[LEFT]["params"]),
    }
    for name in NUMERIC:
        mine = got[name]
        if name.endswith("Price"):
            mine = mine.astype(np.float32).view(np.int32)
        same[name] = np.array_equal(mine, want[name])
    return [f"frame {f}: column {c!r} differs from the per-event reference"
            for c, ok in same.items() if not ok]
