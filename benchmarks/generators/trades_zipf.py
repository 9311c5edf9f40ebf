"""`trades_zipf`: the `trades` row (generators/trades.py: symbol, price,
volume and whatever creation stamps `event_index_attributes` names) with the
symbols drawn by a Zipf law instead of uniformly: symbol id `k` (0-based)
has weight `(k + 1) ** -skew` over `keys` ids, drawn from the seed by
inverse CDF (one uniform per event, one `searchsorted` into the cumulative
weights, which are built once per `(keys, skew)`).

Why: over a window of tens of millions of rows a uniform draw over any key
count a host can intern puts every key inside the window all the time, so
the window's distinct count is a constant that a broken count would also
give. With skew 1.1 over 1,000,000 keys the tail comes and goes: about
958,000 distinct in 60,000,000 rows, moving row by row, about 29,900
distinct strings a 131,072-row frame.

Prices, volumes, the wire form and the strings are `trades`' own; a frame
is a function of (seed, stream, producer, slot) and nothing else.
"""

from __future__ import annotations

import functools

import numpy as np

import registry

_trades = registry.load_module("generators", "trades")
symbol_strings = _trades.symbol_strings
wire_columns = _trades.wire_columns


@functools.lru_cache(maxsize=2)
def _cumulative(keys: int, skew: float) -> np.ndarray:
    weights = np.arange(1, keys + 1, dtype=np.float64) ** -skew
    cdf = np.cumsum(weights)
    return cdf / cdf[-1]


def columns(params: dict, seed: int, stream: str, producer: int,
            slot: int) -> dict:
    """The frame's events as plain columns: symbol ids, prices, volumes."""
    rng = _trades._rng(seed, stream, producer, slot)
    n = params["rows_per_frame"]
    cdf = _cumulative(params["keys"], float(params["skew"]))
    ids = np.searchsorted(cdf, rng.random(n), side="right")
    return {
        "symbol": np.minimum(ids, params["keys"] - 1).astype(np.int64),
        "price": rng.integers(params["price_lo"], params["price_hi"], n)
        * params["price_step"],
        "volume": rng.integers(params["volume_lo"], params["volume_hi"], n),
    }
