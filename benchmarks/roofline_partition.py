"""Least bytes and operations of one `partition` step — one frame of B
events of `partition_1m` routed to their devices' `#window.length(10)`
windows, `max(temp)` taken over each event's window, the events written
into their devices' rings — from the deployment's shapes alone, in
roofline.py's style (whose peaks and `least_seconds` it uses).

There is no Pallas kernel and no matrix multiplication in the step: it is a
table lookup, a sort by slot, gathers and a scatter, bound by memory. The
least it must move per lane (= per input event):

  read   the input row       ts i64 8 + valid 1 + type 1 + deviceID i64 8
                             + roomNo i32 4 + temp f32 4 + timestamp i64 8
                                                                   = 34 B
  key    the stored id the lane's is compared with (two words)      =  8 B
         and the slot it stands for                                 =  4 B
  read   the device's L = 10 window values (temp f32, one a row)    = 40 B
  write  the event's packed row into the device's ring: deviceID 2
         + roomNo 1 + temp 1 + timestamp 2 + ts 2 words of 4 B      = 32 B
  state  the device's count, read and written                       =  8 B
  write  the output row      ts i64 8 + valid 1 + type 1
                             + timestamp i64 8 + roomNo i32 4
                             + deviceID i64 8 + maxTemp f32 4       = 34 B

160 B a lane. **The ring's 537 MB and the table's 67 MB are not in it**: a
step that costs the batch touches B devices' rows and never the ring.
Random accesses (the id, the window values, the row written) are priced at
their size, as if HBM served single words at its streaming rate; it does
not, so this is far below what any gather-and-scatter program can reach, and
the share says how far the step is from streaming its own data, not from a
reachable bound. What the step actually moves — a whole 128-word bucket of
the table a lane, the device's whole 128-word row fetched and written back
(its ten rows of 8 words and its count, a tile), a sort of the lanes by
slot, the batch's rows gathered into that order — is what the share prices.
Arithmetic is a few compares per window value: bytes bound it.
"""

from __future__ import annotations

PARTITION_IN_ROW_BYTES = 8 + 1 + 1 + 8 + 4 + 4 + 8
PARTITION_KEY_BYTES = 8 + 4  # the stored id compared, its slot
PARTITION_VALUE_BYTES = 4  # one window value (temp as float32)
PARTITION_PACKED_ROW_BYTES = 4 * (2 + 1 + 1 + 2 + 2)
PARTITION_COUNT_BYTES = 2 * 4  # read and write
PARTITION_OUT_ROW_BYTES = 8 + 1 + 1 + 8 + 4 + 8 + 4
PARTITION_OPS_PER_LANE = 2 + 2  # a key compare and a max, per word pair


def partition_step(batch: int, length: int) -> dict:
    """Least bytes and operations of one step over `batch` lanes whose
    windows hold `length` rows."""
    per_lane = (PARTITION_IN_ROW_BYTES + PARTITION_KEY_BYTES
                + length * PARTITION_VALUE_BYTES + PARTITION_PACKED_ROW_BYTES
                + PARTITION_COUNT_BYTES + PARTITION_OUT_ROW_BYTES)
    return {"bytes": per_lane * batch,
            "ops": PARTITION_OPS_PER_LANE * length * batch}
