"""The end-to-end arithmetic, on the producers' and the callback's logs
alone (host clock, `time.monotonic_ns` on both sides). Nothing here reads a
number the program reports about itself."""

from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    return float(np.percentile(np.asarray(samples, np.float64), q))


def frame_lookup(frames: dict, key: str, frame_numbers: np.ndarray):
    """`frames[key]` at the given frame numbers, and which were found (the
    log is ordered by frame number; warm-up and closing frames are not in
    it)."""
    known = frames["frame"]
    if not known.size:
        return (np.zeros(frame_numbers.size, np.int64),
                np.zeros(frame_numbers.size, bool))
    pos = np.minimum(np.searchsorted(known, frame_numbers), known.size - 1)
    return frames[key][pos], known[pos] == frame_numbers


def block_latency_ms(frames: dict, delivered: dict, stride: int,
                     t0_ns: int, t_end_ns: int, since: str = "due_ns"):
    """One sample per block delivered to the callback inside the window:
    delivery time minus the `since` time (due, or reply) of the frame that
    holds the block's newest input event. From the due time it includes
    generator lateness, the POST, ring wait, every stage and read-back, and
    excludes window length: a block's newest event has waited for no
    window. `stride` is the event indexes per frame number."""
    t = delivered["enter_ns"]
    inside = (t >= t0_ns) & (t < t_end_ns) & (delivered["max_ts"] >= 0)
    start, found = frame_lookup(frames, since,
                                delivered["max_ts"][inside] // stride)
    return (t[inside][found] - start[found]) / 1e6


def backlog(run: dict) -> dict:
    """Offered minus completed input events over the window's second half
    against its first: what find_knee.py reads. Growth is in events/s."""
    frames, done = run["frames"], run["reference"].completed
    t0, t_end = run["t0_ns"], run["t_end_ns"]
    mid = (t0 + t_end) // 2

    def offered(lo: int, hi: int) -> int:
        due = frames["due_ns"]
        return int(frames["rows"][(due >= lo) & (due < hi)].sum())

    first = offered(t0, mid) - done(run, t0, mid)
    second = offered(mid, t_end) - done(run, mid, t_end)
    return {"first_half_events": first, "second_half_events": second,
            "second_half_growth_events_per_s": second / (run["seconds"] / 2),
            "offered_events_per_s": offered(t0, t_end) / run["seconds"]}


def rate_by_part(run: dict, parts: int = 4) -> list:
    """`completed` per second over each of `parts` equal parts of the
    window: whether a run's rate is steady inside the run (then a run that
    differs from the next differs as a whole, and a longer window would not
    bring them together)."""
    t0, t_end = run["t0_ns"], run["t_end_ns"]
    edges = [t0 + (t_end - t0) * i // parts for i in range(parts + 1)]
    return [run["reference"].completed(run, a, z) * 1e9 / (z - a)
            for a, z in zip(edges, edges[1:])]


def lateness_ms(frames: dict) -> np.ndarray:
    """How late each sent frame left against its schedule."""
    sent = frames["status"] != -1
    return (frames["send_ns"][sent] - frames["due_ns"][sent]) / 1e6


def post_ms(frames: dict) -> np.ndarray:
    ok = frames["status"] == 200
    return (frames["done_ns"][ok] - frames["send_ns"][ok]) / 1e6
