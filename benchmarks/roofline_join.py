"""Least bytes and operations of one `join` step — one frame of B events of
either stream of `join_100k` probing the opposite `length(W)` window and
being appended to its own — from the deployment's shapes alone, in
roofline.py's style (whose peaks and `least_seconds` it uses).

There is no Pallas kernel and no matrix multiplication in the step: it is
gathers and scatters, bound by memory. The least it must move per lane
(= per input event), with `pairs` = W / keys expected matches a probe:

  read   the input row       ts i64 8 + valid 1 + type 1 + symbol i32 4
                             + price f32 4 + volume i64 8 + timestamp i64 8
                                                                   = 34 B
  write  its packed row into its own window's ring: symbol 1 + price 1
         + volume 2 + timestamp 2 + ts 2 words of 4 B               = 32 B
  write  four multimap words: the slot's next, hash and arrival
         index, and its bucket's head                               = 16 B
  probe  the bucket head                                            =  4 B
         and 12 B a chain entry (arrival index, hash, next) for the
         expected chain: the matches alone, as a hash table with no
         collisions would have it                          = 12 B x pairs
  gather one matched packed row per expected pair          = 32 B x pairs
  write  one output row per expected pair: ts i64 8 + valid 1 + type 1
         + symbol i32 4 + tradePrice f32 4 + quotePrice f32 4
         + tradeStamp i64 8 + quoteStamp i64 8             = 38 B x pairs

168 B a lane at one expected match. **Random 4-byte accesses are priced at
their element size**, as if HBM served single words at its streaming rate;
it does not (a gather moves a burst per element), so this is far below what
any gather-and-scatter program can reach, and the share says how far the
step is from streaming its own data, not from a reachable bound. What the
step actually moves — a stable argsort over the batch, a 16-step chain walk
of three 131072-lane gathers each, a cumsum and a scatter over B x 16
candidate lanes into a 4 x B block, and the selector over all 4 x B lanes
whatever they hold — is what the share prices. Arithmetic is a hash, a few
compares and index sums per lane: bytes bound it.
"""

from __future__ import annotations

JOIN_IN_ROW_BYTES = 8 + 1 + 1 + 4 + 4 + 8 + 8
JOIN_PACKED_ROW_BYTES = 4 * (1 + 1 + 2 + 2 + 2)
JOIN_MULTIMAP_WRITE_BYTES = 4 * 4
JOIN_HEAD_BYTES = 4
JOIN_CHAIN_ENTRY_BYTES = 12
JOIN_OUT_ROW_BYTES = 8 + 1 + 1 + 4 + 4 + 4 + 8 + 8
JOIN_OPS_PER_LANE = 24  # hash mix, masks, index sums; per pair 8 more


def join_step(batch: int, window: int, keys: int) -> dict:
    """Least bytes and operations of one join step over `batch` lanes."""
    pairs = window / keys  # uniform keys: expected matches a probe
    per_lane = (JOIN_IN_ROW_BYTES + JOIN_PACKED_ROW_BYTES
                + JOIN_MULTIMAP_WRITE_BYTES + JOIN_HEAD_BYTES
                + pairs * (JOIN_CHAIN_ENTRY_BYTES + JOIN_PACKED_ROW_BYTES
                           + JOIN_OUT_ROW_BYTES))
    return {"bytes": per_lane * batch,
            "ops": (JOIN_OPS_PER_LANE + 8 * pairs) * batch}
