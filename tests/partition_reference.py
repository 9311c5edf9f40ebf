"""The plain reference of a value partition over a `length` window, per
event, independent of the program (tests only; the benchmark keeps its own
copy in benchmarks/references/partition_1m.py, as the harness's isolation
asks).

Upstream's partition gives every key its own instance of the inner query:
`partition with (key of S) begin from S#window.length(L) select ...
<agg>(x) ... end` keeps a FIFO of the last L events per key, and every
arriving event emits one row carrying the aggregate over its key's window,
itself included. Outputs interleave in arrival order.
"""

from __future__ import annotations

from collections import deque

AGGREGATES = {
    "sum": lambda w: sum(w),
    "count": lambda w: len(w),
    "avg": lambda w: sum(w) / len(w),
    "min": lambda w: min(w),
    "max": lambda w: max(w),
}


class KeyedLengthWindows:
    """`dict[key] -> deque(maxlen=L)`; `capacity` keys at most: an event of
    a key that arrives when `capacity` others hold the slots is turned away
    (no row), and counted."""

    def __init__(self, length: int, capacity=None) -> None:
        self.length = length
        self.capacity = capacity
        self.windows: dict = {}
        self.turned_away = 0

    def arrive(self, key, value):
        """One event; its key's window after it (None: turned away)."""
        window = self.windows.get(key)
        if window is None:
            if self.capacity is not None \
                    and len(self.windows) >= self.capacity:
                self.turned_away += 1
                return None
            window = self.windows[key] = deque(maxlen=self.length)
        window.append(value)
        return window


def keyed_window_aggregates(keys, values, length: int, aggregate: str = "max",
                            capacity=None):
    """Per event, in the order given: the aggregate over its key's last
    `length` values, or None for an event turned away. Returns (rows,
    turned away)."""
    state = KeyedLengthWindows(length, capacity)
    fn = AGGREGATES[aggregate]
    rows = []
    for k, v in zip(keys, values):
        w = state.arrive(k, v)
        rows.append(None if w is None else fn(w))
    return rows, state.turned_away
