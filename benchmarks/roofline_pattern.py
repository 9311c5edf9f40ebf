"""Least bytes and operations of the two `pattern` steps of `pattern_ab` —
`every t=A -> q=B[q.symbol == t.symbol]`: one frame of B trades appended to
the pending table, one frame of B quotes matched against it — from the
deployment's shapes alone, in roofline.py's style (whose peaks and
`least_seconds` it uses). Written against the shapes, not against the
program: it reads the same whatever implements the step.

There is no Pallas kernel and no matrix multiplication in either step: they
are sorts, scans, gathers and scatters, bound by memory. With `keys` uniform
symbols, a quote frame holds a given symbol with probability
`hit = 1 - exp(-B / keys)`, so in the steady state every trade waits for
1 / hit quote frames and a quote frame takes out `B` trades (as many as a
trade frame brought).

A trade (A) step, per lane (= per input event):

  read   the input row       ts i64 8 + valid 1 + type 1 + symbol i32 4
                             + price f32 4 + volume i64 8 + timestamp i64 8
                                                                   = 34 B
  write  the partial match   the four captured attributes 24 + the frame's
         into a free slot    ts 8 and valid 1 + start ts 8 + last seq 8
                             + valid 1                              = 50 B
  find   the free slot       one word of a free list                =  4 B

A quote (B) step:

  read   the input row, per lane                                    = 34 B
  probe  per lane: one table word (is a trade of this symbol
         waiting, and where), as a hash table with no collisions
         would have it                                              =  4 B
  per match (B of them a frame): read the partial match 50 B, clear its
         slot 1 B, write the output row: ts i64 8 + valid 1 + type 1
         + symbol i32 4 + tradePrice f32 4 + quotePrice f32 4
         + tradeStamp i64 8 + quoteStamp i64 8 = 38 B               = 89 B

88 B a lane for a trade step, 127 B a lane for a quote step. **Random
accesses are priced at their size**, as if HBM served single words at its
streaming rate; it does not, so the share says how far the steps are from
streaming their own data, not from a reachable bound. What the steps
actually move — the table's validity swept for free slots and for expiry, a
sort of the frame's and the table's keys, scans over both, a table-wide
output block whatever it holds — is what the share prices. Arithmetic is a
few compares and index sums per lane: bytes bound it.
"""

from __future__ import annotations

IN_ROW_BYTES = 8 + 1 + 1 + 4 + 4 + 8 + 8
PENDING_ENTRY_BYTES = (4 + 4 + 8 + 8) + 8 + 1 + 8 + 8 + 1
FREE_SLOT_BYTES = 4
PROBE_BYTES = 4
OUT_ROW_BYTES = 8 + 1 + 1 + 4 + 4 + 4 + 8 + 8
OPS_PER_LANE = 16  # compares, masks, index sums


def trade_step(batch: int) -> dict:
    """Least bytes and operations of one A step over `batch` lanes."""
    return {"bytes": (IN_ROW_BYTES + PENDING_ENTRY_BYTES + FREE_SLOT_BYTES)
            * batch, "ops": OPS_PER_LANE * batch}


def quote_step(batch: int) -> dict:
    """Least bytes and operations of one B step over `batch` lanes, in the
    steady state: as many matches leave as a trade frame brought."""
    return {"bytes": (IN_ROW_BYTES + PROBE_BYTES + PENDING_ENTRY_BYTES + 1
                      + OUT_ROW_BYTES) * batch,
            "ops": 2 * OPS_PER_LANE * batch}
