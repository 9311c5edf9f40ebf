"""Plain per-event reference for a stream-stream windowed equi-join,

    from L#window.length(W) as l join R#window.length(W) as r
    on l.key == r.key select ... insert into Out;

independent of the program: no jax, no numpy, one event at a time. An
arriving event looks up the opposite side's live rows with its key, gives
one pair per match, oldest match first (upstream's `find()` order,
JoinProcessor.java:140-143), and is then appended to its own side's window,
which evicts its oldest row once it holds more than W.

The engine serialises whole frames (one micro-batch probes the opposite
window as it stood before the batch, never its own side), so a frame's
events do not see each other: `frame()` is `arrive()` event by event.
benchmarks/references/join_100k.py keeps a copy of its own (the benchmark
shares no code with tests/), and benchmarks/tests holds the two to each
other.
"""

from __future__ import annotations

from collections import deque

LEFT, RIGHT = 0, 1


class WindowedJoin:
    """Both windows of one join. An event is (key, payload); a pair is
    (left payload, right payload) whichever side triggered."""

    def __init__(self, window: int) -> None:
        self.window = window
        self._rows = (deque(), deque())  # per side: keys in arrival order
        self._by_key = ({}, {})  # per side: key -> deque of payloads

    def arrive(self, side: int, key, payload) -> list:
        """The pairs this event gives, then its own window's append."""
        found = self._by_key[1 - side].get(key)
        if found is None:
            pairs = []
        elif side == LEFT:
            pairs = [(payload, other) for other in found]
        else:
            pairs = [(other, payload) for other in found]
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
        return pairs

    def frame(self, side: int, keys, payloads) -> list:
        """One frame's pairs: by probe event, a probe's matches oldest
        first."""
        # the whole frame probes the opposite window as it stands: events
        # of one side never meet each other
        out = []
        for key, payload in zip(keys, payloads):
            out.extend(self.arrive(side, key, payload))
        return out
