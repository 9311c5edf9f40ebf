"""Plain per-event reference for the keyed two-step pattern,

    from every a=A -> b=B[b.key == a.key] within W
    select ... insert into Out;

independent of the program: no jax, no numpy, no batches, one event at a
time. Per key a queue of waiting A events; an A event appends; a B event
first lets go of every waiting A, of any key, that is older than the bound
against ITS OWN timestamp (`b.ts - a.ts > W`: upstream's `isExpired` of each
pending partial match against the arriving event), then pops every waiting A
of its key and gives one pair for each, oldest first (upstream walks its
pending list in arrival order for each arriving event). An A is therefore
matched with the FIRST later B of its key, exactly once, unless a B stamped
beyond its bound arrived first — that B included.

Nothing here knows of frames: `frame()` is a loop over a frame's events.
benchmarks/references/pattern_ab.py keeps a copy of its own (the benchmark
shares no code with tests/), and benchmarks/tests holds the two to each
other.
"""

from __future__ import annotations

import heapq
from collections import deque

A, B = 0, 1


class EveryAThenB:
    """An event is (key, timestamp, payload); a pair is (A's payload, B's
    payload)."""

    def __init__(self, within=None) -> None:
        self.within = within
        self._waiting: dict = {}  # key -> deque of [timestamp, payload, waits]
        self._oldest: list = []  # heap of (timestamp, arrival, entry)
        self._arrivals = 0
        self._live = 0
        self.let_go = 0  # waiting A events the bound has let go so far

    def waiting(self) -> int:
        return self._live

    def arrive_a(self, key, ts, payload) -> None:
        entry = [ts, payload, True]
        q = self._waiting.get(key)
        if q is None:
            self._waiting[key] = deque((entry,))
        else:
            q.append(entry)
        if self.within is not None:
            heapq.heappush(self._oldest, (ts, self._arrivals, entry))
            self._arrivals += 1
        self._live += 1

    def arrive_b(self, key, ts, payload) -> list:
        """The pairs this B event completes."""
        oldest = self._oldest
        while oldest and ts - oldest[0][0] > self.within:
            entry = heapq.heappop(oldest)[2]
            if entry[2]:  # still waiting: let go
                entry[2] = False
                self._live -= 1
                self.let_go += 1
        pairs = []
        for entry in self._waiting.pop(key, ()):
            if entry[2]:
                entry[2] = False
                self._live -= 1
                pairs.append((entry[1], payload))
        return pairs

    def frame(self, side: int, keys, stamps, payloads) -> list:
        """One frame's pairs, its events taken one at a time. An A frame
        gives none."""
        out = []
        for key, ts, payload in zip(keys, stamps, payloads):
            if side == A:
                self.arrive_a(key, ts, payload)
            else:
                out.extend(self.arrive_b(key, ts, payload))
        return out
