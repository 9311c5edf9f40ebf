"""The plain reference of a sliding time window's distinctCount, per event,
independent of the program (tests only; the benchmark keeps its own copy in
benchmarks/references/distinct_60s.py, as the harness's isolation asks).

Upstream's TimeWindowProcessor is a FIFO: per arriving event the clock moves
to the running maximum of the stamps (@app:playback), the head is popped
while `head.stamp + W <= clock` — the walk stops at the first head that is
not due, whatever lies behind it — then the arrival is appended and the
query emits one row: the number of distinct symbols in the window.
"""

from __future__ import annotations

from collections import Counter, deque


class DistinctTimeWindow:
    def __init__(self, width: int) -> None:
        self.width = width
        self.clock = None
        self.fifo: deque = deque()
        self.counts: Counter = Counter()

    def due(self) -> bool:
        return bool(self.fifo) and self.fifo[0][1] + self.width <= self.clock

    def pop(self) -> None:
        symbol, _ = self.fifo.popleft()
        self.counts[symbol] -= 1
        if not self.counts[symbol]:
            del self.counts[symbol]

    def expire(self) -> int:
        """Pop what is due at the clock; returns how many rows left."""
        left = 0
        while self.due():
            self.pop()
            left += 1
        return left

    def arrive(self, symbol, stamp: int) -> int:
        """One event; returns the distinct count its output row carries."""
        self.clock = stamp if self.clock is None else max(self.clock, stamp)
        self.expire()
        self.fifo.append((symbol, stamp))
        self.counts[symbol] += 1
        return len(self.counts)

    def timer(self, now: int) -> int:
        """A heartbeat: the clock moves to `now`; returns the rows let go."""
        self.clock = now if self.clock is None else max(self.clock, now)
        return self.expire()


def distinct_counts(symbols, stamps, width: int) -> list:
    """Output of `#window.time(width) select distinctCount(symbol)` for the
    events in the order given."""
    w = DistinctTimeWindow(width)
    return [w.arrive(s, int(t)) for s, t in zip(symbols, stamps)]
