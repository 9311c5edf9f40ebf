"""Multi-host execution over DCN — jax.distributed bring-up + mesh builder.

Reference counterpart: none — the reference is single-JVM (SURVEY §2.5); its
scale-out story is keyed partitions and sharded aggregations, which this
framework already runs over an ICI mesh (parallel/sharded.py,
core/aggregation.py mesh mode). This module extends the SAME mesh programming
model across hosts: every host runs the same single-controller program,
`jax.distributed` connects the processes, and `global_mesh()` lays the
partition axis over ALL devices so shard_map collectives ride ICI within a
slice and DCN across slices — exactly the "pick a mesh, annotate shardings,
let XLA insert collectives" recipe.

Deployment (one process per host, identical code):

    from siddhi_tpu.parallel.multihost import init_distributed, global_mesh

    init_distributed(coordinator="10.0.0.1:8476",
                     num_processes=4, process_id=HOST_INDEX)
    mesh = global_mesh()                      # all hosts' devices, one axis
    rt = SiddhiManager().create_siddhi_app_runtime(app, mesh=mesh, ...)

Each host feeds ITS OWN events through its InputHandlers; key-hash ownership
(parallel/sharded.shard_owned) makes every shard process only its keys, so a
round-robin (or any) external partitioner in front of the hosts yields the
same results as one big host. On-demand reads that merge shards
(aggregation find(), partition state) execute as global programs — call them
from every process collectively, per SPMD rules.

Caveats (documented, enforced where cheap):
- all hosts must run the SAME app and the SAME sequence of global programs
  (standard jax multi-process discipline);
- host-side state (tables without mesh sharding, record stores, string
  interning) is per-host; multi-host apps should key all cross-host state by
  the mesh (partitions, sharded aggregations) or an external store;
- this module only wires processes together — single-host multi-chip apps
  never need it.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def init_distributed(coordinator: str, num_processes: int, process_id: int,
                     local_device_ids: Optional[list[int]] = None) -> None:
    """Connect this process to the jax.distributed cluster (idempotent).

    coordinator: "host:port" of process 0; every process passes the same.
    """
    import jax

    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )


def global_mesh(axis_name: str = "part"):
    """One-axis mesh over every device of every connected process — the
    partition/shard axis used by mesh-enabled runtimes. Within a slice the
    axis rides ICI; across slices XLA routes collectives over DCN."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()), (axis_name,))


def global_lane_batch(codec, timestamps, cols: dict, mesh, key_attrs,
                      lane_width: int):
    """Per-host SHARDED ingestion: encode THIS host's rows, route each to
    its owning shard (the same key-hash rule as shard_owned), and assemble
    one lane-sharded global EventBatch via
    jax.make_array_from_process_local_data — each host moves only its own
    bytes over DCN (SURVEY §2.5's per-host half; replicated ingestion
    re-encodes the full stream on every host).

    Contract: this host's rows must be OWNED by this host's addressable
    shards (an external key partitioner in front of the hosts); rows owned
    elsewhere are dropped with a count in the returned tuple. STRING key
    columns must intern to IDENTICAL codes on every host (pre-encode the
    symbol universe in one agreed order).

    Returns (global_batch, n_dropped_foreign)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..core.event import EventBatch
    from .sharded import np_shard_of

    axis = mesh.axis_names[0]
    n_shards = mesh.shape[axis]
    ts = np.asarray(timestamps, dtype=np.int64)
    n = ts.shape[0]
    enc = codec.encode_columns(cols, n)
    shard_of = np_shard_of([enc[a] for a in key_attrs], n_shards)

    mesh_flat = list(mesh.devices.flat)
    local_ids = [i for i, d in enumerate(mesh_flat)
                 if d.process_index == jax.process_index()]
    n_local = len(local_ids)
    dropped = 0

    lane_ts = np.zeros((n_local, lane_width), np.int64)
    lane_valid = np.zeros((n_local, lane_width), bool)
    lane_cols = {k: np.zeros((n_local, lane_width), v.dtype)
                 for k, v in enc.items()}
    truncated = 0
    for li, sid in enumerate(local_ids):
        idx = np.nonzero(shard_of == sid)[0]
        if idx.size > lane_width:
            import warnings
            truncated += idx.size - lane_width
            warnings.warn(
                f"global_lane_batch: shard {sid} got {idx.size} rows but "
                f"lane_width={lane_width}; excess dropped — raise "
                "lane_width or split the send", stacklevel=2)
            idx = idx[:lane_width]
        m = idx.size
        lane_ts[li, :m] = ts[idx]
        lane_valid[li, :m] = True
        for k in lane_cols:
            lane_cols[k][li, :m] = enc[k][idx]
    # total rows NOT ingested: foreign-shard rows + lane-width truncation
    dropped = int(np.sum(~np.isin(shard_of, local_ids))) + truncated

    sharding = NamedSharding(mesh, P(axis))

    def put(local2d):
        flat = local2d.reshape(n_local * lane_width)
        return jax.make_array_from_process_local_data(
            sharding, flat, (n_shards * lane_width,))

    batch = EventBatch(
        ts=put(lane_ts),
        cols={k: put(v) for k, v in lane_cols.items()},
        valid=put(lane_valid),
        types=put(np.zeros((n_local, lane_width), np.int8)),
    )
    return batch, dropped


def is_coordinator() -> bool:
    """True on process 0 — the conventional place for host-only side effects
    (REST service, persistence-store writes, log sinks)."""
    import jax

    return jax.process_index() == 0
