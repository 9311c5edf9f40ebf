"""Plain reference for `pattern_ab`: the keyed two-step pattern,

    from every t=cseEventStream -> q=quoteEventStream[q.symbol == t.symbol]
    within 5000 sec
    select t.symbol as symbol, t.price as tradePrice, q.price as quotePrice,
           t.timestamp as tradeStamp, q.timestamp as quoteStamp
    insert into matchedStream;

Per event (`EveryAThenB`, no numpy, nothing of the program, no frames): per
symbol a queue of waiting trades (A events); a trade appends; a quote (B
event) first lets go of every waiting trade, of any symbol, that is older
than the bound against ITS OWN timestamp (`q.ts - t.ts > within`: upstream's
`isExpired` of each pending partial match against the arriving event), then
pops every waiting trade of its symbol and gives one row for each, oldest
first. A trade is matched with the FIRST later quote of its symbol, exactly
once, unless a quote stamped beyond its bound arrived first.

**The result depends on how the engine serialised the two streams' frames**,
so `checks.OneToOne` does not fit and this file answers run.py's four
questions itself. The engine runs a frame (= one micro-batch) as a whole;
the reference takes the frames' events one at a time in that order.

The serialisation is read from the callback's log. Trade frames yield no
block. Quote frames stand in block order (a block's timestamps are its
completing events' global indexes, so each block names its quote frame); a
trade frame stands before the first block that holds a row of it, among the
trade frames of one gap in the order the rows show (a quote's matches come
oldest first) and else in number order; one none of whose rows was delivered
stands after the last block; an accepted quote frame with no block stands
right before its producer's next answered frame and may stand only where the
replay too yields no row. The replay of that order must then reproduce every
block, so a wrong inference fails.

- `account(run)`: the engine's drop counter first (a run that lost partial
  matches ends there); then over EVERYTHING delivered, vectorised: the
  replay's rows for every quote frame against its block's `tradeStamp` /
  `quoteStamp` / timestamps, row for row and in order. That holds every row
  to the rule: no trade twice, none that arrived after its quote or beyond
  the bound, none let go within it, no quote skipped for a later one of its
  symbol, a quote's matches oldest first, every accepted quote frame
  answered by exactly one block; and each producer's frames in order.
- `verify_sample(run, rng)`: >= 64 seeded blocks, row for row, in order, all
  five columns and the timestamp bit for bit against the per-event loop.
- `completed(run, lo, hi)`: a trade counts when its row is delivered in the
  span, a quote frame's events when its block is.
"""

from __future__ import annotations

import functools
import gc
import heapq
import itertools
from collections import deque

import numpy as np

import record

A, B = 0, 1  # trades, quotes: the configuration's input order
SAMPLE = 64  # blocks checked row by row, at least (or all there are)
NUMERIC = ("tradePrice", "quotePrice", "tradeStamp", "quoteStamp")
DROP_COUNTER = "pattern_pending_dropped"


# ------------------------------------------------- the per-event reference


class EveryAThenB:
    """An event is (key, timestamp, payload); a pair is (A's payload, B's
    payload). One event per turn: the sample runs it over 64 blocks of
    131,072 quotes and as many waiting trades, so the two kinds of arrival
    are written out in `frame`'s loops, not called."""

    def __init__(self, within=None) -> None:
        self.within = within
        self._waiting: dict = {}  # key -> [[timestamp, payload, waits]]
        self._oldest: list = []  # heap of (timestamp, arrival, entry)
        self._arrivals = 0

    def frame(self, side: int, keys, stamps, payloads) -> list:
        """One frame's pairs, its events taken one at a time: by completing
        event, a B's matches oldest first. An A frame gives none."""
        waiting, within, oldest = self._waiting, self.within, self._oldest
        push, pop = heapq.heappush, heapq.heappop
        out: list = []
        if side == A:
            arrival = self._arrivals
            for key, ts, payload in zip(keys, stamps, payloads):
                # an A event waits, behind the older ones of its key
                entry = [ts, payload, True]
                q = waiting.get(key)
                if q is None:
                    waiting[key] = [entry]
                else:
                    q.append(entry)
                if within is not None:
                    push(oldest, (ts, arrival, entry))
                    arrival += 1
            self._arrivals = arrival
            return out
        for key, ts, payload in zip(keys, stamps, payloads):
            # a B event: first every waiting A older than the bound against
            # this event's timestamp is let go, whatever its key
            while oldest and ts - oldest[0][0] > within:
                pop(oldest)[2][2] = False
            # then every waiting A of its key leaves as a pair, oldest first
            for entry in waiting.pop(key, ()):
                if entry[2]:
                    entry[2] = False
                    out.append((entry[1], payload))
        return out


# ------------------------------------------------------- the serialisation


def _within(run: dict) -> int:
    return int(run["config"]["within_ticks"])


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
    """Quote frame of every delivered block, -1 where a block's rows do not
    all complete in one frame."""
    cached = run.get("pattern_block_frames")
    if cached is None:
        stride = run["events"].stride
        cached = run["pattern_block_frames"] = np.array(
            [int(b.timestamps[0]) // stride
             if b.count and int(b.timestamps.min()) // stride
             == int(b.timestamps.max()) // stride else -1
             for b in run["delivered"]["blocks"]], np.int64)
    return cached


def _trade_frames(run: dict) -> list:
    """Per block: the trade frames its rows name and how many rows each,
    as two arrays."""
    cached = run.get("pattern_trade_frames")
    if cached is None:
        stride = run["events"].stride
        cached = run["pattern_trade_frames"] = []
        for b in run["delivered"]["blocks"]:
            frame = b.column("tradeStamp") // stride
            lo = int(frame.min()) if b.count else 0
            counts = np.bincount(frame - lo)
            named = np.nonzero(counts)[0]
            cached.append((named + lo, counts[named]))
    return cached


def _older_first(run: dict) -> set:
    """(f, g): some quote's matches list a trade of frame f right before
    one of frame g, so f ran before g."""
    stride = run["events"].stride
    pairs: set = set()
    for b in run["delivered"]["blocks"]:
        if b.count < 2:
            continue
        trade = b.column("tradeStamp") // stride
        quote = b.column("quoteStamp")
        step = (quote[1:] == quote[:-1]) & (trade[1:] != trade[:-1])
        if step.any():
            pairs.update(zip(trade[:-1][step].tolist(),
                             trade[1:][step].tolist()))
    return pairs


def serialisation(run: dict) -> dict:
    """`order`: every accepted frame in the order the engine ran them;
    `block_of`: quote frame -> index of the block that answers it;
    `failures`: in words, where the log cannot be a serialisation of what
    was accepted."""
    cached = run.get("pattern_serialisation")
    if cached is not None:
        return cached
    events = run["events"]
    accepted = _accepted(run)
    side = {f: events.source(f)[0] for f in accepted}
    answered = _block_frames(run).tolist()
    fails: list = []
    block_of: dict = {}
    for b, f in enumerate(answered):
        if side.get(f) != B:
            fails.append(f"block {b} answers no single accepted quote "
                         f"frame ({f})")
        elif f in block_of:
            fails.append(f"frame {f} is answered by blocks {block_of[f]} "
                         f"and {b}")
        else:
            block_of[f] = b
    # trade frames: before the first block that holds a row of them
    first_block: dict = {}
    for b, (named, _) in enumerate(_trade_frames(run)):
        for f in named.tolist():
            if side.get(f) != A:
                fails.append(f"block {b} names frame {f}, no accepted "
                             "trade frame")
            first_block.setdefault(f, b)
    older = _older_first(run)

    def by_the_rows(f: int, g: int) -> int:
        if (f, g) in older and (g, f) not in older:
            return -1
        if (g, f) in older and (f, g) not in older:
            return 1
        return f - g

    gaps: dict = {}
    for f in sorted(f for f, s in side.items() if s == A):
        gaps.setdefault(first_block.get(f, len(answered)), []).append(f)
    for gap in gaps.values():
        gap.sort(key=functools.cmp_to_key(by_the_rows))
    # quote frames with no block: right before their producer's next
    # answered frame (each producer's frames keep their order)
    producer_of = {f: events.source(f)[:2] for f in accepted}
    waiting: dict = {}
    for f in sorted(f for f, s in side.items() if s == B):
        waiting.setdefault(producer_of[f], deque()).append(f)
    order: list = []
    for b, f in enumerate(answered):
        order += gaps.get(b, [])
        if block_of.get(f) != b:
            continue
        mine = waiting[producer_of[f]]
        if f not in mine:
            continue  # placed already: its producer's order broke below
        while mine[0] != f:
            skipped = mine.popleft()
            if skipped in block_of:
                fails.append(f"frame {skipped} of producer "
                             f"{producer_of[f]} ran after its frame {f}")
            order.append(skipped)
        order.append(mine.popleft())
    order += gaps.get(len(answered), [])
    for mine in waiting.values():
        order.extend(mine)  # never answered: they stand last
    # each producer's trade frames in order, too
    last: dict = {}
    for f in order:
        if side[f] == A:
            if last.get(producer_of[f], -1) > f:
                fails.append(f"trade frame {f} of producer "
                             f"{producer_of[f]} ran after its frame "
                             f"{last[producer_of[f]]}")
            last[producer_of[f]] = f
    cached = run["pattern_serialisation"] = {
        "order": order, "block_of": block_of, "side": side,
        "failures": fails}
    return cached


class Replay:
    """Walks a serialisation with the waiting trades as two arrays (symbol,
    global index), oldest first: a trade frame appends, a quote frame takes
    out what it matches, and the order of the rest stands."""

    def __init__(self, run: dict) -> None:
        self.events = run["events"]
        self.within = _within(run)
        self.stride = self.events.stride
        self.let_go = 0
        self.keys = np.zeros(0, np.int64)
        self.stamps = np.zeros(0, np.int64)

    def rows(self, f: int) -> int:
        return self.events.plan_of(f)["rows"]

    def waiting(self) -> int:
        return int(self.keys.size)

    def run_frame(self, f: int, side: int, rows: bool = True):
        """Advance over frame `f`. A trade frame: None. A quote frame: the
        rows it owes as (tradeStamp, quoteStamp), in the block's order
        (`rows=False`: the caller wants the state moved on, no more)."""
        n = self.rows(f)
        keys = self.events.frame_columns(f)["symbol"]
        if side == A:
            self.keys = np.concatenate([self.keys, keys])
            self.stamps = np.concatenate(
                [self.stamps, f * self.stride + np.arange(n, dtype=np.int64)])
            return None
        # first lane of every symbol in the frame (-1: not in it)
        first = np.full(int(keys.max()) + 1, -1, np.int64)
        first[keys[::-1]] = np.arange(n - 1, -1, -1, dtype=np.int64)
        lane = np.where(self.keys < first.size,
                        first[np.minimum(self.keys, first.size - 1)], -1)
        hit = lane >= 0
        # a frame's stamps rise lane by lane, so the newest stamp a waiting
        # trade has seen arrive when its own quote does (or, with none in
        # the frame, at the frame's end) is that lane's: older than the
        # bound against it, the trade was let go on the way
        last_seen = f * self.stride + np.where(hit, lane, n - 1)
        kept = last_seen - self.stamps <= self.within
        self.let_go += int((~kept).sum())
        match = hit & kept
        trade, lane = self.stamps[match], lane[match]
        stay = kept & ~hit
        self.keys, self.stamps = self.keys[stay], self.stamps[stay]
        if not rows:
            return None
        # by completing event, then by the partial match's arrival: as one
        # key, because a sort of distinct keys runs three times as fast as
        # a stable one
        by_lane = np.argsort((lane << 32) | np.arange(lane.size))
        return trade[by_lane], f * self.stride + lane[by_lane]

    def waiting_for(self, f: int) -> tuple:
        """(keys, timestamps) of the waiting trades, oldest first, whose
        symbol frame `f` holds: the only ones its quotes can reach."""
        mine = np.isin(self.keys, self.events.frame_columns(f)["symbol"])
        return self.keys[mine].tolist(), self.stamps[mine].tolist()


# ------------------------------------------------------- run.py's questions


def expected_output_rows(run: dict, sent_frames) -> int:
    """A lower bound only, which a run that drops partial matches reaches
    too: how many rows the pattern owes depends on the serialisation, and
    `rt.drain()` returns after the last callback has. One row at least per
    quote frame after the warm-up. The frames' columns are regenerated here,
    which the account needs anyway."""
    events = run["events"]
    quotes = 0
    for f in sent_frames:
        events.frame_columns(f)
        quotes += events.source(f)[0] == B
    return max(0, quotes - events.warm)


def _dropped(run: dict) -> dict:
    return {k: v for k, v in (run["stats_end"].get("overflow") or {}).items()
            if k.endswith(DROP_COUNTER)}


def completed(run: dict, lo_ns: int, hi_ns: int) -> float:
    """Input events (of both streams) whose results reached the callback in
    [lo, hi): a measured trade when its row is delivered then, a measured
    quote frame's events when its block is (with no block owed, when the
    next block of any frame is)."""
    frames, delivered = run["frames"], run["delivered"]
    answered = _block_frames(run)
    if not frames["frame"].size or not answered.size:
        return 0.0
    ok = frames["status"] == 200
    n_frames = int(max(frames["frame"].max(), answered.max(),
                       max((int(n.max()) for n, _ in _trade_frames(run)
                            if n.size), default=0))) + 1
    rows_of = np.zeros(n_frames, np.int64)  # measured frames only
    rows_of[frames["frame"][ok]] = frames["rows"][ok]
    t = delivered["enter_ns"]
    inside = (t >= lo_ns) & (t < hi_ns)
    done = 0.0
    for b in np.nonzero(inside)[0].tolist():
        named, counts = _trade_frames(run)[b]
        known = (named >= 0) & (named < n_frames)  # a wrong row may name any
        done += float(counts[known][rows_of[named[known]] > 0].sum())
        if answered[b] >= 0:
            done += float(rows_of[answered[b]])
    # measured quote frames that owed no block: with the next block
    ser = run.get("pattern_serialisation")
    if ser is not None:
        block_of, at = ser["block_of"], None
        for f in reversed(ser["order"]):
            if f in block_of:
                at = block_of[f]
            elif ser["side"][f] == B and at is not None and inside[at] \
                    and f < n_frames:
                done += float(rows_of[f])
    return done


def account(run: dict) -> dict:
    events, frames = run["events"], run["frames"]
    stats_end = run["stats_end"]
    blocks = run["delivered"]["blocks"]
    refused = int(frames["rows"][frames["status"] != 200].sum())
    attempted = int(frames["rows"].sum())
    drops = _dropped(run)
    if drops:
        # a run that lost partial matches is not replayed: every later row
        # would differ, and the replay of a table a twentieth full says
        # nothing more
        return {"checks": {"pattern_pending_dropped_zero": False},
                "conserved": False,
                "failures": [f"the engine dropped partial matches: {drops}"],
                "attempted": attempted, "failed": attempted,
                "detail": {"dropped": drops, "blocks": len(blocks)}}
    ser = serialisation(run)
    order, block_of, side = ser["order"], ser["block_of"], ser["side"]
    accepted = _accepted(run)
    replay = Replay(run)
    measured = set(frames["frame"][frames["status"] == 200].tolist())
    sent_by_stream = [0] * len(events.plans)
    expected = rows_out = 0
    unanswered: list = []
    differing: list = []
    failed_measured = 0
    high_water = 0
    for f in order:
        sent_by_stream[side[f]] += replay.rows(f)
        owed = replay.run_frame(f, side[f])
        high_water = max(high_water, replay.waiting())
        if owed is None:
            continue
        trade, quote = owed
        expected += trade.size
        b = block_of.get(f)
        if b is None:
            if trade.size:
                unanswered.append(f)
                failed_measured += trade.size * (f in measured)
            continue
        blk = blocks[b]
        rows_out += blk.count
        same = blk.count == trade.size \
            and np.array_equal(blk.column("tradeStamp"), trade) \
            and np.array_equal(blk.column("quoteStamp"), quote) \
            and np.array_equal(blk.timestamps, quote)
        if not same:
            differing.append((f, blk.count, int(trade.size)))
            failed_measured += max(blk.count, trade.size) * (f in measured)
    answered_twice = len(blocks) - len(block_of)
    pipes = stats_end.get("ingress_pipeline") or {}
    rows_in = [(pipes.get(plan["stream"]) or {}).get("rows_in")
               for plan in events.plans]
    sent_rows = sum(sent_by_stream)
    checks = {
        "accepted_equals_sent": sum(accepted.values()) == sent_rows,
        "pipeline_rows_in_equals_sent": rows_in == sent_by_stream,
        "ingress_dropped_zero": not stats_end.get("ingress_dropped"),
        "log_is_a_serialisation": not ser["failures"],
        "every_quote_frame_answered_once":
            not unanswered and answered_twice == 0,
        "rows_out_equal_the_reference_count": rows_out == expected,
        "every_block_is_the_replays_rows_in_order": not differing,
        "pattern_pending_dropped_zero": True,
        "no_expired_rows": not any(bool(b.is_expired.any())
                                   for b in blocks),
    }
    fails = [f"conservation check {k} failed"
             for k, v in checks.items() if not v]
    fails += ser["failures"][:5]
    fails += [f"frame {f} has no block, the reference has rows for it"
              for f in unanswered[:5]]
    fails += [f"frame {f}: {got} rows, not the replay's {want} in its order"
              for f, got, want in differing[:5]]
    return {
        "checks": checks,
        "conserved": all(checks.values()),
        "failures": fails,
        "attempted": attempted,
        "failed": refused + int(failed_measured),
        "detail": {"sent_rows": sent_rows,
                   "accepted": int(sum(accepted.values())),
                   "rows_in": rows_in, "frames": len(order),
                   "blocks": len(blocks), "rows_expected": expected,
                   "rows_out": rows_out, "blocks_differing": len(differing),
                   "waiting_high_water": high_water,
                   "waiting_at_end": replay.waiting(),
                   "let_go": replay.let_go,
                   "dropped": drops, "refused_events": refused},
    }


def verify_sample(run: dict, rng) -> dict:
    if _dropped(run):
        return {"failures": [], "sampled": 0, "unit": "blocks"}
    blocks = run["delivered"]["blocks"]
    ser = serialisation(run)
    order, block_of, side = ser["order"], ser["block_of"], ser["side"]
    answered = [f for f in order if f in block_of]
    picks = set(rng.choice(answered, min(SAMPLE, len(answered)),
                           replace=False).tolist()) if answered else set()
    replay = Replay(run)
    fails: list = []
    # the per-event loop makes a list and a tuple an event and no cycle;
    # beside a run's logs the collector's passes over them double its time
    collecting = gc.isenabled()
    gc.disable()
    try:
        for f in order:
            if f in picks:
                fails += _compare(run, f, blocks[block_of[f]], replay)
            replay.run_frame(f, side[f], rows=False)
    finally:
        if collecting:
            gc.enable()
    return {"failures": fails, "sampled": len(picks), "unit": "blocks"}


def _compare(run: dict, f: int, blk, replay: Replay) -> list:
    """One block against the per-event loop: the trades waiting at this
    point arrive in their order at a pattern of its own, then the frame's
    quotes."""
    events = run["events"]
    stride = events.stride
    pattern = EveryAThenB(replay.within)
    keys, stamps = replay.waiting_for(f)
    pattern.frame(A, keys, stamps, stamps)
    n = replay.rows(f)
    stamps = list(range(f * stride, f * stride + n))
    pairs = pattern.frame(B, events.frame_columns(f)["symbol"].tolist(),
                          stamps, stamps)
    both = np.fromiter(itertools.chain.from_iterable(pairs), np.int64,
                       2 * len(pairs)).reshape(-1, 2)
    trade, quote = both[:, A], both[:, B]
    if blk.count != len(pairs):
        return [f"frame {f}: {blk.count} rows, the per-event reference "
                f"gives {len(pairs)}"]
    want = {
        "event timestamp": quote, "tradeStamp": trade, "quoteStamp": quote,
        "tradePrice": events.lookup(trade, ("price",))["price"]
        .astype(np.float32).view(np.int32),
        "quotePrice": events.lookup(quote, ("price",))["price"]
        .astype(np.float32).view(np.int32),
    }
    got = record.gather([(blk, 0, blk.count)], NUMERIC, ("symbol",))
    same = {
        "event timestamp": np.array_equal(got["ts"], want["event timestamp"]),
        "symbol": got["symbol"] == events.gens[A].symbol_strings(
            events.lookup(trade, ("symbol",))["symbol"],
            events.plans[A]["params"]),
    }
    for name in NUMERIC:
        mine = got[name]
        if name.endswith("Price"):
            mine = mine.astype(np.float32).view(np.int32)
        same[name] = np.array_equal(mine, want[name])
    return [f"frame {f}: column {c!r} differs from the per-event reference"
            for c, ok in same.items() if not ok]
