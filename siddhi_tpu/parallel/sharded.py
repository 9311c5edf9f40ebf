"""Sharded (multi-chip) query execution over a `jax.sharding.Mesh`.

Reference counterpart: `partition with (attr of Stream)` clones query runtimes
per key and routes events by key (PartitionStreamReceiver.java:82-141,
PartitionRuntimeImpl.java:75) — thread-level data parallelism inside one JVM.

The TPU-native redesign: the partition key space is **hashed onto a mesh
axis**. Every device holds a shard of the query state (group tables, window
rings); each micro-batch is broadcast to all devices and each device masks the
batch down to the lanes it owns (`hash(key) % n_shards == my_shard`). Because
filters/windows/selectors are all mask-based, shard-local execution is just the
ordinary single-chip step on a thinner mask — no per-key cloning, no routing
queues. Output lanes are disjoint across shards, so the merged output is an
`psum` over the mesh axis of zero-masked columns (one XLA collective riding
ICI, not host gather).

`ShardedQueryStep` below shards ONE query's state by key hash (each shard runs
the ordinary step on the lanes it owns — keys co-located on a shard share that
shard's state, matching unpartitioned GROUP BY semantics at scale).

`PartitionedQueryStep` is the `partition with (key of Stream)` runtime over a
mesh: state carries a leading KEY-SLOT axis (`[n_slots, ...]` pytree), sharded
over the mesh axis with `shard_map` and vmapped over the local slots — every
key gets its own fully isolated window/selector/limiter state, exactly the
reference's per-key runtime clones, but as one SPMD step (SURVEY §7 "a key
axis in state arrays"). Keys map to slots through a replicated device
KeyTable in first-appearance order.

This module is used by the driver's `dryrun_multichip`, by
`core/partition.py` when a mesh is configured, and by tests on a virtual
CPU mesh; the same code compiles unchanged for a real TPU slice.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.event import EventBatch
from ..ops.groupby import (DenseKeyTable, dense_key_lookup_or_insert,
                           hash_columns, init_dense_key_table)


def np_shard_of(key_cols, n_shards: int):
    """HOST-side (numpy) mirror of `shard_owned`'s key-hash ownership —
    per-host sharded ingestion routes rows to their owning shard BEFORE
    device_put, so the device mask is a no-op guard. Must stay bit-exact
    with ops/groupby.hash_columns."""
    import numpy as np
    with np.errstate(over="ignore"):
        h = np.uint64(0xCBF29CE484222325)
        h = np.broadcast_to(h, np.shape(key_cols[0])).copy()
        for c in key_cols:
            c = np.asarray(c)
            if c.dtype.kind == "f":
                bits = c.view(np.int32 if c.dtype == np.float32
                              else np.int64)
                x = bits.astype(np.int64).astype(np.uint64)
            else:
                x = c.astype(np.int64).astype(np.uint64)
            h = (h ^ x) * np.uint64(0x100000001B3)
            h = h ^ (h >> np.uint64(29))
        keys = h.astype(np.int64)
        return keys.astype(np.uint32) % np.uint32(n_shards)


def shard_owned(batch: EventBatch, key_cols, axis_name: str,
                n_shards: int) -> EventBatch:
    """Mask a replicated batch down to the lanes THIS shard owns by key-hash
    ownership. The single definition of shard assignment — queries
    (ShardedQueryStep) and distributed aggregations must agree on it."""
    my_shard = jax.lax.axis_index(axis_name)
    keys = hash_columns(key_cols)
    owned = (keys.astype(jnp.uint32) % n_shards) == my_shard.astype(jnp.uint32)
    return batch.where_valid(owned)


def _zero_masked(batch: EventBatch) -> EventBatch:
    """Zero every lane that is invalid so cross-shard psum merges cleanly."""
    v = batch.valid
    return EventBatch(
        ts=jnp.where(v, batch.ts, 0),
        cols={k: jnp.where(v, c, jnp.zeros((), c.dtype)) for k, c in batch.cols.items()},
        valid=v,
        types=jnp.where(v, batch.types, 0).astype(jnp.int8),
    )


def merge_shard_outputs(out: EventBatch, axis_name: str) -> EventBatch:
    """psum-merge disjoint per-shard outputs into the full output batch."""
    z = _zero_masked(out)
    return EventBatch(
        ts=jax.lax.psum(z.ts, axis_name),
        cols={k: jax.lax.psum(c, axis_name) for k, c in z.cols.items()},
        valid=jax.lax.psum(z.valid.astype(jnp.int8), axis_name) > 0,
        types=jax.lax.psum(z.types.astype(jnp.int32), axis_name).astype(jnp.int8),
    )


def stack_states(state, n_shards: int):
    """Replicate a single-shard init state into an [n_shards, ...] stacked
    pytree (each shard starts from the same empty state)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_shards,) + jnp.shape(x)), state)


class ShardedQueryStep:
    """Wraps a pure per-query step `(state, batch, now) -> (state', out)` into
    an SPMD step over `mesh[axis_name]`, partitioned by a key-column hash.

    `key_attrs` are the partition-key column names in the input batch.
    """

    def __init__(self, step_fn: Callable, mesh: Mesh, axis_name: str,
                 key_attrs: Sequence[str]):
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_shards = mesh.shape[axis_name]
        self.key_attrs = tuple(key_attrs)

        n_shards = self.n_shards

        def shard_step(state, batch: EventBatch, now):
            # state arrives with a leading local axis of size 1 — unstack
            local = jax.tree_util.tree_map(lambda x: x[0], state)
            mine = shard_owned(batch, [batch.cols[a] for a in self.key_attrs],
                               axis_name, n_shards)
            local, out = step_fn(local, mine, now)
            merged = merge_shard_outputs(out, axis_name)
            restacked = jax.tree_util.tree_map(lambda x: x[None], local)
            return restacked, merged

        state_spec = P(axis_name)
        repl = P()
        self._step = jax.jit(
            shard_map(
                shard_step, mesh=mesh,
                in_specs=(state_spec, repl, repl),
                out_specs=(state_spec, repl),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    def init_state(self, single_state):
        """Place a replicated-from-empty stacked state onto the mesh."""
        stacked = stack_states(single_state, self.n_shards)
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), stacked)

    def __call__(self, state, batch: EventBatch, now):
        return self._step(state, batch, now)


class PartitionedQueryStep:
    """`partition with (key of Stream)` over a mesh: a key-slot axis in state.

    Wraps a pure per-query step `(state, batch, now) -> (state', out)` so that
    `n_slots` independent copies of its state live stacked on a leading axis,
    sharded over `mesh[axis_name]`; each step vmaps the query over the local
    slots with per-slot lane masks. A lane belongs to exactly one slot (dense
    id from a replicated KeyTable, assigned in first-appearance order), so
    every partition key has fully isolated window/selector/limiter state —
    the reference's per-key QueryRuntime clones
    (PartitionStreamReceiver.java:82-141) as one SPMD step.

    An all-invalid batch acts as a timer heartbeat: every slot's step runs
    with `now`, so per-key time windows flush without a host loop over keys.

    The merged output is the per-slot outputs flattened to one
    `[n_slots * chunk_width]` batch, ordered by slot id (key first-appearance
    order) — the host loop it replaces orders by sorted key value, both are
    batched reorderings of the reference's arrival-order interleave.
    """

    def __init__(self, step_fn: Callable, mesh: Mesh, axis_name: str,
                 n_slots: int, key_fn: Callable[[EventBatch], jax.Array]):
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_shards = mesh.shape[axis_name]
        if n_slots % self.n_shards != 0:
            raise ValueError(
                f"partition capacity {n_slots} must be divisible by the mesh "
                f"axis size {self.n_shards}")
        self.n_slots = n_slots
        slots_local = n_slots // self.n_shards

        def shard_step(states, batch: EventBatch, slots, now):
            base = jax.lax.axis_index(axis_name).astype(jnp.int32) * slots_local

            def per_slot(state, j):
                owned = batch.valid & (slots == base + j)
                return step_fn(state, batch.where_valid(owned), now)

            return jax.vmap(per_slot)(
                states, jnp.arange(slots_local, dtype=jnp.int32))

        spec, repl = P(axis_name), P()
        sharded = shard_map(
            shard_step, mesh=mesh,
            in_specs=(spec, repl, repl, repl),
            out_specs=(spec, spec),
            check_vma=False,
        )

        def full_step(states, key_table: DenseKeyTable, batch: EventBatch, now):
            keys = key_fn(batch)
            key_table, slots = dense_key_lookup_or_insert(
                key_table, keys, batch.valid)
            states, outs = sharded(states, batch, slots, now)
            # flatten [n_slots, C] per-slot outputs into one wide batch
            flat = jax.tree_util.tree_map(
                lambda x: x.reshape((-1,) + x.shape[2:]), outs)
            return states, key_table, flat

        self._step = jax.jit(full_step, donate_argnums=(0, 1))

    def init_state(self, single_state):
        """Stack the per-key template state onto the sharded slot axis."""
        stacked = stack_states(single_state, self.n_slots)
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        return (
            jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding), stacked),
            init_dense_key_table(self.n_slots),
        )

    def __call__(self, states, key_table, batch: EventBatch, now):
        return self._step(states, key_table, batch, now)
