"""Plain reference for `filter_700`: upstream's
SimpleFilterSingleQueryPerformance,

    from cseEventStream[700 > price]
    select symbol, price, volume, timestamp insert into outputStream;

Every event with `700 > price` gives one row (its symbol, its float price,
its volume and its creation stamp, which the producer set to the event's
global index), in its producer's order. A seeded sample of frames is
checked row by row; conservation (checks.py: one row per answered event)
covers the rest.
"""

from __future__ import annotations

import numpy as np

import checks
import record

PRICE_CUT = 700.0
SAMPLE = 64  # frames checked row by row, at least (or all there are)


def passes(cols: dict, config: dict, stream: str) -> np.ndarray:
    return PRICE_CUT > cols["price"]


def expected_rows(passed: int, config: dict) -> int:
    return passed


_FAMILY = checks.OneToOne(passes, expected_rows)
account = _FAMILY.account
completed = _FAMILY.completed
expected_output_rows = _FAMILY.expected_output_rows


def verify_sample(run: dict, rng) -> dict:
    delivered, events = run["delivered"], run["events"]
    rows = events.stride
    blocks = delivered["blocks"]
    lo = np.array([int(b.timestamps.min()) // rows if b.count else -1
                   for b in blocks], np.int64)
    hi = np.array([int(b.timestamps.max()) // rows if b.count else -1
                   for b in blocks], np.int64)
    frames = np.unique(np.concatenate([lo, hi])) if blocks else np.zeros(0)
    frames = frames[frames >= 0]
    picks = np.sort(rng.choice(frames, min(SAMPLE, frames.size),
                               replace=False)) if frames.size else []
    fails: list = []
    for f in picks.tolist() if frames.size else []:
        segments = []
        for b in np.nonzero((lo <= f) & (f <= hi))[0].tolist():
            mine = np.nonzero(blocks[b].timestamps // rows == f)[0]
            if mine.size:
                # a frame's rows sit together inside a block
                segments.append((blocks[b], int(mine[0]), int(mine[-1]) + 1))
        got = record.gather(segments, ("price", "volume", "timestamp"),
                            ("symbol",))
        cols = events.frame_columns(f)
        plan = events.plan_of(f)
        keep = np.nonzero(passes(cols, run["config"], plan["stream"]))[0]
        same = {
            "event timestamp": np.array_equal(got["ts"], f * rows + keep),
            "symbol": got["symbol"] == events.gens[
                events.source(f)[0]].symbol_strings(
                cols["symbol"][keep], plan["params"]),
            "price": np.array_equal(
                got["price"].astype(np.float32),
                cols["price"][keep].astype(np.float32)),
            "volume": np.array_equal(got["volume"], cols["volume"][keep]),
            "timestamp": np.array_equal(got["timestamp"], got["ts"]),
        }
        fails.extend(f"frame {f}: column {c!r} differs from the reference"
                     for c, ok in same.items() if not ok)
    return {"failures": fails, "sampled": len(picks), "unit": "frames"}
