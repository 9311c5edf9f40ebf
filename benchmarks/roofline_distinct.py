"""Least bytes and operations of one `distinct` step — one frame of B events
of `distinct_60s` appended to the sliding time window's ring, as many rows
(in the steady state: the window is full, so a row leaves for each that
comes) taken out of its far end, and both run through distinctCount's
pair-count table — from the deployment's shapes alone, in roofline.py's
style (whose peaks and `least_seconds` it uses).

There is no Pallas kernel and no matrix multiplication in the step: it is
slices of the ring, a sort by symbol, gathers and scatters, bound by memory.
The least it must move per lane (= per input event):

  read   the input row       ts i64 8 + valid 1 + type 1 + symbol i32 4
                             + price f32 4 + volume i64 8 + timestamp i64 8
                                                                   = 34 B
  write  its packed row into the ring: symbol 1 + price 1 + volume 2
         + timestamp 2 + ts 2 words of 4 B                          = 32 B
  read   the packed row that leaves the ring's far end              = 32 B
  state  the arriving symbol's pair count (i64) read and written    = 16 B
         and the expiring symbol's                                  = 16 B
  write  the output row      ts i64 8 + valid 1 + type 1
                             + timestamp i64 8 + distinctSymbols i64 8
                                                                   = 26 B

156 B a lane. **The ring's 2.15 GB are not in it**: a step that costs the
batch touches B rows at each end of the ring and never the ring. Random
8-byte accesses to the pair-count table are priced at their element size, as
if HBM served single words at its streaming rate; it does not, so this is
far below what any gather-and-scatter program can reach, and the share says
how far the step is from streaming its own data, not from a reachable
bound. What the step actually moves — a chunk of B + E lanes (E the expiry
width, four frames) whatever it holds, a stable sort of the chunk by symbol,
segment scans, an emulated-int64 scatter into the 2^21-slot table, a binary
search of the batch's clocks in E deadlines — is what the share prices.
Arithmetic is a few compares and index sums per lane: bytes bound it.
"""

from __future__ import annotations

DISTINCT_IN_ROW_BYTES = 8 + 1 + 1 + 4 + 4 + 8 + 8
DISTINCT_PACKED_ROW_BYTES = 4 * (1 + 1 + 2 + 2 + 2)
DISTINCT_PAIR_COUNT_BYTES = 2 * 8  # read and write, per lane touching it
DISTINCT_OUT_ROW_BYTES = 8 + 1 + 1 + 8 + 8
DISTINCT_OPS_PER_LANE = 16  # clock max, deadline compare, 2 count updates


def distinct_step(batch: int) -> dict:
    """Least bytes and operations of one step over `batch` arriving lanes
    and as many expiring ones."""
    per_lane = (DISTINCT_IN_ROW_BYTES + 2 * DISTINCT_PACKED_ROW_BYTES
                + 2 * DISTINCT_PAIR_COUNT_BYTES + DISTINCT_OUT_ROW_BYTES)
    return {"bytes": per_lane * batch, "ops": DISTINCT_OPS_PER_LANE * batch}
