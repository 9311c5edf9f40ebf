"""Operations and bytes the benchmark's kernels need, from their shapes, and
the least time a chip could take for them (peaks/<device_kind>.json, one
file per kind of chip with its source; spaces in the kind become `_`). Kept with the benchmark so that no PR that claims a gain can
change the yardstick.

The only "kernel" today is the `agg` step: the XLA program of
`from MidStream#window.lengthBatch(W) select symbol, sum(price), avg(price),
count() group by symbol` over one batch of B lanes. There is no Pallas
kernel and no matrix multiplication in it; it is bound by memory, and the
least it must move per lane is

  read   the input batch    ts i64 8 + valid 1 + type 1 + symbol i32 4
                            + price f32 4 + volume i64 8           = 26 B
  write  the output batch   ts i64 8 + valid 1 + type 1 + symbol i32 4
                            + total f32 4 + avgPrice f32 4 + n i64 8 = 30 B
  state  one group's running (sum f32 4, count i64 8) read and written
         once per event                                           = 24 B

80 bytes a lane: nothing else has to cross HBM, because a lengthBatch
window resets its groups when it closes, so the 2^20-slot group table need
never be swept, and ordering rows inside a window needs no sort by key when
the running values are kept per slot. What the step actually moves (a stable
argsort over the batch, segment scans, table-wide resets) is what the share
prices. Arithmetic is a handful of adds and one divide per lane, four orders
of magnitude under the compute peak, so bytes bound it.
"""

from __future__ import annotations

import json
import os

PEAKS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks")
AGG_BYTES_PER_LANE = 26 + 30 + 24
AGG_OPS_PER_LANE = 8  # compare, two adds, convert, divide, select, 2 index


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind. A kind with no file is
    an error, never a default."""
    path = os.path.join(PEAKS_DIR, device_kind.replace(" ", "_") + ".json")
    try:
        with open(path) as fh:
            table = json.load(fh)
    except FileNotFoundError:
        raise KeyError(f"no peaks/{os.path.basename(path)} for device kind "
                       f"{device_kind!r}; add one with its source") from None
    if table["device_kind"] != device_kind:
        raise KeyError(f"{path} is for {table['device_kind']!r}, not "
                       f"{device_kind!r}")
    return table


def agg_step(batch: int) -> dict:
    """Least bytes and operations of one `agg` step over `batch` lanes."""
    return {"bytes": AGG_BYTES_PER_LANE * batch,
            "ops": AGG_OPS_PER_LANE * batch}


def least_seconds(work: dict, device_kind: str) -> dict:
    """The larger of bytes over peak bytes/s and operations over peak
    operations/s, and which of the two bounds."""
    peak = peaks(device_kind)
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["ops"] / peak["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "memory" if by_bytes >= by_ops else "compute"}
