"""What one run leaves behind, in the form the checks, the end-to-end
arithmetic and the per-layer readers all take: the producers' merged frame
log, the callback's block log, and a way back from an event's timestamp
(its global index) to the columns the generator made for it."""

from __future__ import annotations

import time

import numpy as np

import registry
import sxf1


class Events:
    """Input events by global index, regenerated from the seed on demand.
    The producers of all input streams are numbered in one list and share
    one frame numbering, so an event's timestamp names its frame
    (`index // stride`), its stream and its producer, whatever stream it
    came in on. Knows nothing of the query."""

    def __init__(self, plans: list, seed: int, warm: int) -> None:
        self.plans = plans
        self.gens = [registry.load_module("generators", p["generator"])
                     for p in plans]
        self.seed = seed
        self.warm = warm  # warm-up frames per stream, the parent's own
        self.warm_total = warm * len(plans)
        #: global producer index -> (plan number, producer within it)
        self.flat = [(s, p) for s, plan in enumerate(plans)
                     for p in range(plan["producers"])]
        self.producers = len(self.flat)
        #: event indexes per frame number
        self.stride = max(plan["rows"] for plan in plans)
        self.round_events = sum(plans[s]["rows"] for s, _ in self.flat)
        #: frames outside the producers' numbering (closing frames):
        #: frame number -> (plan number, producer, slot)
        self.overrides: dict = {}
        #: for the checks: whatever they derive per generated frame
        self.memo: dict = {}
        self._cache: dict = {}

    def producer_specs(self) -> list:
        """What each producer process needs to know of the numbering."""
        specs, lead = [], 0
        for index, (s, p) in enumerate(self.flat):
            plan = self.plans[s]
            specs.append({
                "index": index, "producer": p, "producers": self.producers,
                "stream": plan["stream"], "generator": plan["generator"],
                "params": plan["params"], "pool": plan["pool"],
                "warm": self.warm_total, "stride": self.stride,
                "round_events": self.round_events, "lead_events": lead,
                "seed": self.seed})
            lead += plan["rows"]
        return specs

    def source(self, f: int) -> tuple:
        """(plan number, producer, pool slot) whose generated columns frame
        `f` carries. Warm-up frames are numbered stream by stream and
        belong to a virtual producer one past each stream's own."""
        if f in self.overrides:
            return self.overrides[f]
        if f < self.warm_total:
            s, slot = divmod(f, self.warm)
            return s, self.plans[s]["producers"], slot
        k, index = divmod(f - self.warm_total, self.producers)
        s, p = self.flat[index]
        return s, p, k % self.plans[s]["pool"]

    def plan_of(self, f: int) -> dict:
        return self.plans[self.source(f)[0]]

    def frame_columns(self, f: int) -> dict:
        src = self.source(f)
        cols = self._cache.get(src)
        if cols is None:
            s, p, slot = src
            plan = self.plans[s]
            cols = self._cache[src] = self.gens[s].columns(
                plan["params"], self.seed, plan["stream"], p, slot)
        return cols

    def wire_frame(self, f: int, typecodes) -> bytearray:
        """Frame `f` as the parent itself posts it (warm-up, closing)."""
        s = self.source(f)[0]
        plan = self.plans[s]
        body = sxf1.encode_frame(self.gens[s].wire_columns(
            self.frame_columns(f), typecodes, plan["params"]), plan["rows"])
        sxf1.patch_timestamps(body, f * self.stride)
        return body

    def lookup(self, ts: np.ndarray, names) -> dict:
        """Columns `names` of the events with timestamps `ts`, in that
        order."""
        frames = ts // self.stride
        rows = ts % self.stride
        out = None
        for f in np.unique(frames):
            cols = self.frame_columns(int(f))
            if out is None:
                out = {n: np.empty(ts.size, cols[n].dtype) for n in names}
            sel = frames == f
            for n in names:
                out[n][sel] = cols[n][rows[sel]]
        return out or {n: np.zeros(0) for n in names}


class Delivered:
    """The callback's log: per block its arrival time (callback entry and
    exit, monotonic ns) and the block itself, kept whole."""

    def __init__(self) -> None:
        self.enter_ns: list = []
        self.exit_ns: list = []
        self.blocks: list = []
        self.rows = 0

    def on_block(self, block) -> None:
        # the callback does next to nothing: two clock reads, an append and
        # a count
        self.enter_ns.append(time.monotonic_ns())
        self.blocks.append(block)
        self.rows += block.count
        self.exit_ns.append(time.monotonic_ns())

    def wait_rows(self, expected: int, timeout: float) -> None:
        """Until `expected` rows have reached the callback, or `timeout`
        seconds pass (the checks then say what is missing)."""
        deadline = time.monotonic() + timeout
        while (self.rows < expected or len(self.exit_ns) < len(self.blocks)) \
                and time.monotonic() < deadline:
            time.sleep(0.005)

    def arrays(self) -> dict:
        n = len(self.exit_ns)  # blocks whose callback has returned
        blocks = self.blocks[:n]
        rows = np.array([b.count for b in blocks], np.int64)
        return {
            "enter_ns": np.array(self.enter_ns[:n], np.int64),
            "exit_ns": np.array(self.exit_ns[:n], np.int64),
            "rows": rows,
            "row_end": np.cumsum(rows),
            "max_ts": np.array([int(b.timestamps.max()) if b.count else -1
                                for b in blocks], np.int64),
            "blocks": blocks,
        }


def merge_frame_logs(logs: list, events) -> dict:
    """The producers' logs (in the order of their global indexes) as arrays
    over frames, ordered by frame number, with each frame's producer, its
    stream (the plan's number) and its rows."""
    keys = ("frame", "due_ns", "send_ns", "done_ns", "status", "accepted",
            "reconnects")
    empty = [np.zeros(0, np.int64)]
    cat = {k: np.concatenate([np.asarray(lg[k], np.int64) for lg in logs]
                             or empty) for k in keys}
    per_log = {
        "producer": range(len(logs)),
        "stream": [events.flat[i][0] for i in range(len(logs))],
        "rows": [events.plans[events.flat[i][0]]["rows"]
                 for i in range(len(logs))]}
    for k, values in per_log.items():
        cat[k] = np.concatenate([np.full(len(lg["frame"]), v, np.int64)
                                 for lg, v in zip(logs, values)] or empty)
    order = np.argsort(cat["frame"], kind="stable")
    return {k: v[order] for k, v in cat.items()}


def column_slice(block, name: str, lo: int, hi: int) -> list:
    """Strings of rows [lo, hi) of one block's string column: a narrower
    block of the program's own type, so the program's decoder does the
    work and only the sampled rows pay for Python strings."""
    part = type(block)(
        block.timestamps[lo:hi],
        {k: v[lo:hi] for k, v in block.columns.items()},
        block.is_expired[lo:hi], hi - lo, block._codec)
    return part.strings(name)


def row_segments(delivered: dict, lo: int, hi: int) -> list:
    """Rows [lo, hi) of the delivery order as (block, first, last) pieces."""
    ends = delivered["row_end"]
    out = []
    b = int(np.searchsorted(ends, lo, side="right"))
    while lo < hi and b < len(ends):
        start = int(ends[b] - delivered["rows"][b])
        a, z = lo - start, min(hi, int(ends[b])) - start
        if z > a:
            out.append((delivered["blocks"][b], a, z))
        lo = start + z
        b += 1
    return out


def gather(segments: list, numeric: tuple, strings: tuple) -> dict:
    """Timestamps, the named numeric columns and the named string columns
    (decoded) over `row_segments` pieces, concatenated."""
    out = {"ts": np.concatenate([blk.timestamps[a:z]
                                 for blk, a, z in segments])}
    for name in numeric:
        out[name] = np.concatenate([blk.column(name)[a:z]
                                    for blk, a, z in segments])
    for name in strings:
        out[name] = [s for blk, a, z in segments
                     for s in column_slice(blk, name, a, z)]
    return out
