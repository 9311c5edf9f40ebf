"""Plain reference for `groupby_1m`: the query one event at a time, in
Python, independent of siddhi_tpu (the smoke's `reference_rows`).

    from TradeStream[price < 700.0] select symbol, price, volume
        insert into MidStream;
    from MidStream#window.lengthBatch(W)
        select symbol, sum(price) as total, avg(price) as avgPrice,
               count() as n group by symbol insert into SummaryStream;

Keep `price < 700`; cut the kept events, in arrival order, into consecutive
windows of W; inside a window every event emits its symbol's running sum,
average and count; a window's rows come out when it is full. A window
depends only on its own W events, so a seeded sample of windows is checked
per event and conservation (checks.py) covers the rest.
"""

from __future__ import annotations

import numpy as np

import checks
import record

PRICE_CUT = 700.0
SAMPLE = 64  # windows checked per event, at least (or all there are)


def passes(cols: dict, config: dict, stream: str) -> np.ndarray:
    return cols["price"] < PRICE_CUT


def expected_rows(passed: int, config: dict) -> int:
    w = config["sizes"]["window"]
    return w * (passed // w)


_FAMILY = checks.OneToOne(passes, expected_rows)
account = _FAMILY.account
completed = _FAMILY.completed
expected_output_rows = _FAMILY.expected_output_rows


def window_rows(sym, price):
    """One window, per event: running (sum, count) by symbol. float32 sums
    are exact for the generator's prices (multiples of 0.25, small)."""
    total = np.empty(len(sym), np.float32)
    count = np.empty(len(sym), np.int64)
    sums: dict = {}
    counts: dict = {}
    for i, (k, p) in enumerate(zip(sym.tolist(), price.tolist())):
        s = sums.get(k, 0.0) + p
        c = counts.get(k, 0) + 1
        sums[k] = s
        counts[k] = c
        total[i] = s
        count[i] = c
    return total, total / count.astype(np.float32), count


def verify_sample(run: dict, rng) -> dict:
    """The arrival order is read back from the emitted timestamps (each is
    the index of the event it answers); the reference runs over the sampled
    windows' events in that order. Exact, except `avgPrice`: one float32
    division, which the TPU does not round correctly — held to 1 ulp."""
    delivered, events = run["delivered"], run["events"]
    w = run["config"]["sizes"]["window"]
    n_windows = int(delivered["row_end"][-1]) // w if len(
        delivered["row_end"]) else 0
    picks = np.sort(rng.choice(n_windows, min(SAMPLE, n_windows),
                               replace=False)) if n_windows else []
    fails: list = []
    worst_ulp = 0
    for win in picks.tolist() if n_windows else []:
        got = record.gather(record.row_segments(delivered, win * w,
                                                (win + 1) * w),
                            ("total", "avgPrice", "n"), ("symbol",))
        src = events.lookup(got["ts"], ("symbol", "price"))
        if not np.all(src["price"] < PRICE_CUT):
            fails.append(f"window {win}: a row answers an event the filter "
                         "drops")
            continue
        total, avg, count = window_rows(src["symbol"], src["price"])
        ulp = np.abs(got["avgPrice"].astype(np.float32).view(np.int32)
                     .astype(np.int64) - avg.view(np.int32).astype(np.int64))
        worst_ulp = max(worst_ulp, int(ulp.max()))
        same = {
            "symbol": got["symbol"] == events.gens[0].symbol_strings(
                src["symbol"], events.plans[0]["params"]),
            "total": np.array_equal(got["total"], total),
            "n": np.array_equal(got["n"], count),
            "avgPrice": int(ulp.max()) <= 1,
        }
        fails.extend(f"window {win}: column {c!r} differs from the reference"
                     for c, ok in same.items() if not ok)
    return {"failures": fails, "sampled": len(picks), "unit": "windows",
            "avg_max_ulp": worst_ulp}
