"""Least bytes and operations of the two programs of `registry_1m` — the
table write (`jit_table_write_DeviceTable`: one frame of registrations
upserted into the @PrimaryKey table through its device index) and the table
join (`jit_join_probe_left`: one frame of readings probing the index and
enriched from their devices' rows) — from the deployment's shapes alone, in
roofline.py's style (whose peaks and `least_seconds` it uses).

There is no Pallas kernel and no matrix multiplication in either: a bucket
lookup, a sort, gathers and scatters, bound by memory. The least each must
move per lane (= per input event):

write (a registration)
  read   the input row       deviceID i64 8 + roomNo i32 4 + type i32 4
                             + timestamp i64 8                     = 24 B
  key    the stored id the lane's is compared with (two words)      =  8 B
         and the slot it stands for                                 =  4 B
  write  the row: deviceID 8 + roomNo 4 + type 4 + updated 8 + ts 8 = 32 B
                                                                     68 B
join (a reading)
  read   the input row       deviceID i64 8 + roomNo i32 4 + temp f32 4
                             + timestamp i64 8                     = 24 B
  key    the stored id compared (two words) and its slot            = 12 B
  read   the row's roomNo 4 + type 4 + updated 8                    = 16 B
  write  a quarter of an output row (a quarter of the readings are in
         server rooms): deviceID 8 + roomNo 4 + roomType 4 + temp 4
         + timestamp 8 + regStamp 8 = 36 B x 1/4                   =  9 B
                                                                     61 B

**The table's 34 MB of rows and its index's 67 MB are not in it**: a step
touches its lanes' rows and buckets, never the table. Random accesses (the
id, the slot, the row) are priced at their size, as if HBM served single
words at its streaming rate; it does not (a gather moves a burst per index,
a bucket is a 512-byte row), so this is below what any gather-and-scatter
program can reach, and the share says how far each program is from
streaming its own data, not from a reachable bound. Arithmetic is a hash,
a few compares and index sums per lane: bytes bound it.
"""

from __future__ import annotations

WRITE_IN_ROW_BYTES = 8 + 4 + 4 + 8
KEY_BYTES = 8 + 4  # the stored id compared, its slot
WRITE_ROW_BYTES = 8 + 4 + 4 + 8 + 8
JOIN_IN_ROW_BYTES = 8 + 4 + 4 + 8
JOIN_ROW_READ_BYTES = 4 + 4 + 8
JOIN_OUT_ROW_BYTES = 8 + 4 + 4 + 4 + 8 + 8
OPS_PER_LANE = 24  # the hash's mixing, the bucket's compares, index sums


def table_write(rows: int) -> dict:
    """Least bytes and operations of one table write of `rows` events."""
    per_lane = WRITE_IN_ROW_BYTES + KEY_BYTES + WRITE_ROW_BYTES
    return {"bytes": per_lane * rows, "ops": OPS_PER_LANE * rows}


def table_join(rows: int, share_out: float) -> dict:
    """Least bytes and operations of one table join of `rows` readings, a
    `share_out` of which give a row."""
    per_lane = (JOIN_IN_ROW_BYTES + KEY_BYTES + JOIN_ROW_READ_BYTES
                + share_out * JOIN_OUT_ROW_BYTES)
    return {"bytes": per_lane * rows, "ops": OPS_PER_LANE * rows}
