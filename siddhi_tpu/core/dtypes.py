"""Attribute-type → device dtype mapping.

Reference semantics: Siddhi attributes are STRING/INT/LONG/FLOAT/DOUBLE/BOOL/OBJECT
(query/api/definition/Attribute.java). On TPU:

- INT  -> int32            (native)
- LONG -> int64            (requires jax x64; we enable it at package import —
                            timestamps are int64 milliseconds like the reference)
- FLOAT -> float32         (native, VPU/MXU friendly)
- DOUBLE -> float32 by default. Java doubles sequentially accumulated and f64 on
  TPU is software-emulated and ~10x slower; tests use tolerances. Set
  `siddhi_tpu.config.double_dtype = jnp.float64` for bit-closer parity.
- BOOL -> bool_
- STRING -> int32 dictionary codes. Strings are interned host-side per
  (stream, attribute) in a StringTable at ingestion; device sees codes, so
  string equality/group-by are integer ops. Code 0 is reserved for null/missing.
- OBJECT -> host-only (kept in a Python list column; cannot enter device exprs).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from typing import NamedTuple, Optional

from ..errors import SiddhiAppCreationError
from ..query_api.definition import AttributeType

#: sentinel string code for null
NULL_CODE = 0

#: timestamp dtype — milliseconds since epoch, matching the reference's long ts.
TS_DTYPE = jnp.int64


class _Config:
    double_dtype = jnp.float32
    #: default micro-batch capacity per stream (events); the batching unit that
    #: replaces the reference's Disruptor ring (StreamJunction.java:68 batchSize).
    default_batch_size = 8192
    #: default window ring-buffer capacity when not statically inferable.
    default_window_capacity = 1 << 16
    #: default max distinct group-by keys tracked on device per query.
    default_group_capacity = 1 << 20
    #: key slots for mesh-sharded partitions (per-key state is preallocated
    #: for every slot, so this is deliberately small; raise per app)
    default_partition_capacity = 64
    #: default table row capacity (rows are capacity-padded device arrays).
    default_table_capacity = 1 << 16
    #: max matched build rows per probe event in joins (static join fan-out).
    join_max_matches = 16
    #: compacted pair-block width as a multiple of the probe batch size —
    #: total matches per step beyond factor*B are dropped (bounded fan-out)
    join_pair_cap_factor = 4
    #: max concurrent partial matches per pattern position.
    pattern_pending_capacity = 1024
    #: retained groups for `output snapshot ... group by` (rows per snapshot)
    snapshot_group_capacity = 1024
    #: full-window snapshot limiter ring (non-aggregated `output snapshot`);
    #: sized up automatically when the window's own capacity is known
    snapshot_window_capacity = 4096
    #: key slots for keyed session windows (session(gap, key))
    session_key_capacity = 4096
    #: expansion bound for unbounded pattern counts `<m:>`.
    pattern_unbounded_count_extra = 8
    #: mid-pattern `every` (sticky positions): qualifying arrivals advanced
    #: per entry per BATCH (leftover counts into `dropped`; cross-batch
    #: repetition is unbounded/exact)
    pattern_sticky_passes = 4
    #: HyperLogLog registers per group for hll:distinctCount (power of two;
    #: std error ~1.04/sqrt(m))
    hll_registers = 1024
    #: max groups tracked by hll:distinctCount (each holds hll_registers)
    hll_group_capacity = 4096
    #: shape-bucketed dispatch: junctions pad partial micro-batches to the
    #: smallest power-of-two lane bucket >= the staged row count (instead of
    #: always the full batch capacity), so each shape-polymorphic query step
    #: compiles at most log2(batch_size / min_bucket) + 1 executables while
    #: small/heartbeat batches run kernels sized to their data. Disabled
    #: automatically for mesh-sharded apps (bucket widths must stay aligned
    #: with the device mesh).
    shape_buckets = True
    #: smallest bucket capacity in the ladder (power of two)
    min_bucket = 16
    #: debug-mode invariant checks inside jitted steps (also enabled by
    #: SIDDHI_DEBUG_CHECKS=1): currently the windows' nondecreasing
    #: emission-key check before rank-merge scatters (ops/windows.py
    #: _merge_order). Trace-time gated — zero cost when off.
    debug_checks = False


config = _Config()

import os as _os

if _os.environ.get("SIDDHI_DEBUG_CHECKS", "") not in ("", "0"):
    config.debug_checks = True
if _os.environ.get("SIDDHI_SHAPE_BUCKETS", "") == "0":
    config.shape_buckets = False


def bucket_ladder(cap: int) -> tuple[int, ...]:
    """Ascending power-of-two lane-bucket ladder for one junction capacity:
    (min_bucket, 2*min_bucket, ..., cap). `cap` itself is always the top
    rung even when it is not a power of two, so full batches never pad."""
    mb = max(int(config.min_bucket), 1)
    out = []
    b = mb
    while b < cap:
        out.append(b)
        b <<= 1
    out.append(cap)
    return tuple(out)


def bucket_capacity(n: int, cap: int) -> int:
    """Smallest ladder bucket holding `n` valid rows (n == 0 -> min bucket,
    n >= cap -> cap)."""
    if n >= cap:
        return cap
    b = max(int(config.min_bucket), 1)
    while b < n:
        b <<= 1
    return min(b, cap)


def device_dtype(t: AttributeType):
    if t == AttributeType.INT:
        return jnp.int32
    if t == AttributeType.LONG:
        return jnp.int64
    if t == AttributeType.FLOAT:
        return jnp.float32
    if t == AttributeType.DOUBLE:
        return config.double_dtype
    if t == AttributeType.BOOL:
        return jnp.bool_
    if t == AttributeType.STRING:
        return jnp.int32  # dictionary codes
    raise ValueError(f"attribute type {t} has no device dtype (OBJECT is host-only)")


def numpy_dtype(t: AttributeType):
    return np.dtype(device_dtype(t).__name__ if hasattr(device_dtype(t), "__name__") else device_dtype(t))


def null_value(t: AttributeType):
    """Fill value used in padded/invalid lanes."""
    if t in (AttributeType.INT, AttributeType.LONG, AttributeType.STRING):
        return 0
    if t in (AttributeType.FLOAT, AttributeType.DOUBLE):
        return 0.0
    if t == AttributeType.BOOL:
        return False
    return None


def is_numeric(t: AttributeType) -> bool:
    return t in (AttributeType.INT, AttributeType.LONG, AttributeType.FLOAT, AttributeType.DOUBLE)


#: promotion lattice for binary math, mirroring the reference's per-type-pair
#: executor selection (core/executor/math/*): int < long < float < double.
_RANK = {
    AttributeType.INT: 0,
    AttributeType.LONG: 1,
    AttributeType.FLOAT: 2,
    AttributeType.DOUBLE: 3,
}


def promote(a: AttributeType, b: AttributeType) -> AttributeType:
    if not (is_numeric(a) and is_numeric(b)):
        raise TypeError(f"cannot apply arithmetic to {a}/{b}")
    return a if _RANK[a] >= _RANK[b] else b


class StatedCapacity(NamedTuple):
    """What `@capacity(...)` on a query (or a named window) states: a
    pattern's partial matches per position (`pending`), a sliding window's
    ring rows (`window`) and the rows that may leave it in one step
    (`expire`); on a partition, the keys it holds state for (`keys`:
    core/keyed_partition.py); on a table, its rows (`rows`: core/table.py).
    None: the app says nothing, the defaults hold."""

    pending: Optional[int] = None
    window: Optional[int] = None
    expire: Optional[int] = None
    keys: Optional[int] = None
    rows: Optional[int] = None


def stated_capacity(annotations) -> StatedCapacity:
    """The one parser of `@capacity(pending=, window=, expire=, keys=,
    rows=)`; whoever builds the structure validates the number against what
    it sizes."""
    ann = next((a for a in (annotations or ())
                if a.name.lower() == "capacity"), None)
    if ann is None:
        return StatedCapacity()
    stated = {}
    for key in StatedCapacity._fields:
        text = ann.element(key)
        if text is None:
            continue
        try:
            n = int(text)
        except ValueError:
            n = 0
        if n < 1 or n > 2**30:
            raise SiddhiAppCreationError(
                f"@capacity({key}={text!r}): a capacity is a whole number "
                "of rows, from 1 to 2^30")
        stated[key] = n
    return StatedCapacity(**stated)
