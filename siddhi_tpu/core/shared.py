"""Shared-execution groups: N co-resident queries, ONE compiled step.

The execution half of the multi-query optimizer (analysis/optimizer.py is
the plan-level half). `build_shared_groups` walks every junction's receiver
list, finds maximal CONTIGUOUS runs of eligible single-input QueryRuntimes
with the same dispatch shape, and splices each run out for a single
SharedStepGroup receiver. The group traces every member's untracked step
body inside one `jax.jit`:

    fused((s1..sN), batch, now) -> ((s1'..sN'), (out1..outN))

so one junction delivery drives all members, one XLA compile covers the
whole group per shape bucket, and XLA's own CSE computes shared scans /
common subexpressions once — the rewrites the plan pass detects
(shared-scan + predicate vectorization, CSE) fall out of tracing together,
with per-member math EXACTLY the graph the unfused step would run. That is
the parity argument: optimizer-on output is bit-identical to optimizer-off
(tests/test_optimizer_parity.py proves it).

What stays per-member: the state tuple (written back after every fused
step, so SnapshotService / restore / upgrade / collect_overflow see the
unfused layout unchanged), callbacks, output junctions, rate limiting,
latency attribution, and the post-step maintenance hooks. Contiguous-run
formation preserves global delivery order exactly — a fused run replaces
its first member's slot, and receivers outside the run never move.

Queries that would change isolation semantics under fusion are DECLINED
loudly (@breaker, partitions, OBJECT attributes, table dependencies,
custom-aggregate compaction) — the reasons surface through SL114 and
statistics_report()["optimizer"]["declined"].
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..query_api.definition import AttributeType
from . import dtypes
from .event import EventBatch
from .query_runtime import QueryRuntime, _sink_dark, aot_warm
from .stream import Receiver

from ..analysis.optimizer import (
    DECLINE_BREAKER,
    DECLINE_CUSTOM_AGG,
    DECLINE_FAULT,
    DECLINE_JOIN_PATTERN,
    DECLINE_OBJECT,
    DECLINE_PARTITION,
    DECLINE_TABLE,
    SPLICE_DECLINE_CAP,
    SPLICE_DECLINE_SHAPE,
    analyze_sharing,
)


#: default ceiling on members per fused group. XLA compile time (and, on
#: CPU, executable quality) degrade superlinearly with graph size; chunking
#: a 256-query run into ceil(256/cap) groups keeps every graph tractable
#: while the compile count stays O(N/cap) — still sublinear in queries.
_DEFAULT_GROUP_CAP = 32


def group_cap() -> int:
    """Members-per-group ceiling (env SIDDHI_OPTIMIZE_GROUP_CAP, min 2)."""
    try:
        cap = int(os.environ.get("SIDDHI_OPTIMIZE_GROUP_CAP", "")
                  or _DEFAULT_GROUP_CAP)
    except ValueError:
        cap = _DEFAULT_GROUP_CAP
    return max(cap, 2)


def runtime_decline(qr) -> Optional[str]:
    """Why this receiver cannot join a shared group (None = eligible).
    Extends the static taxonomy (analysis/optimizer.py decline_reason) with
    the runtime-only facts: table fallbacks and custom-aggregate state."""
    if type(qr) is not QueryRuntime:
        return DECLINE_JOIN_PATTERN
    if getattr(qr, "_partitioned", False):
        return DECLINE_PARTITION
    if qr.breaker is not None:
        return DECLINE_BREAKER
    if qr.query.input_stream.is_fault:
        return DECLINE_FAULT
    if any(a.type == AttributeType.OBJECT
           for a in qr.input_junction.definition.attributes):
        return DECLINE_OBJECT
    if qr.dep_tables or qr._in_fallbacks:
        return DECLINE_TABLE
    if qr._has_custom_aggs:
        return DECLINE_CUSTOM_AGG
    return None


def _apply_pushdown(qr: QueryRuntime) -> int:
    """Predicate pushdown for the provably-safe shape: a windowless query
    (pass-through emits every surviving arrival as CURRENT, so
    `f | (types != CURRENT)` degenerates to `f`) with no stream functions
    whose computed columns the post filter could read. Moves the compiled
    post-window filters into the pre-window conjunction IN PLACE — both the
    member's own step closure and the fused trace capture these list
    objects, so the rewrite applies to whichever executes. Returns the
    number of predicates moved."""
    from ..ops.windows import PassThroughWindow
    if not isinstance(qr.window, PassThroughWindow):
        return 0
    if qr.pre_window_fns or qr.post_window_fns or not qr.post_filters:
        return 0
    moved = len(qr.post_filters)
    qr.filters.extend(qr.post_filters)
    qr.post_filters.clear()
    return moved


class SharedStepGroup(Receiver):
    """One fused receiver standing in for a contiguous run of member
    QueryRuntimes on the same junction.

    The superstep runner (core/superstep.py) scans groups too: it reuses
    `_steps` (the untracked member step closures) inside its `lax.scan`
    body and `_current_emit_flags()` for its per-dispatch emit/DCE
    revalidation, and replays `_post_step_maintenance` + the equal-share
    telemetry attribution per inner batch — keep those surfaces stable."""

    #: junction._deliver consults this before dispatch; members with
    #: breakers never fuse, so the group itself is never diverted
    breaker = None

    def __init__(self, name: str, members: list[QueryRuntime],
                 junction) -> None:
        assert len(members) >= 2
        self.name = name
        self.members = members
        self.junction = junction
        self.ctx = members[0].ctx
        self._batch_cap = members[0]._batch_cap
        self._bucket_ok = all(m._bucket_ok for m in members)
        self.has_time_semantics = any(m.has_time_semantics for m in members)
        self._batches_seen = 0

        self._steps = [m._make_step(track_compiles=False) for m in members]
        self._emit_flags = self._current_emit_flags()
        self._step = self._make_jit(self._emit_flags)
        self._member_names = [m.name for m in members]
        self._tele_cells = None  # resolved on first telemetry-on batch
        for m in members:
            m._fused_group = self

    def _current_emit_flags(self) -> tuple:
        """Per-member: does anything observe this member's emission? Dark
        members' outputs are DROPPED from the fused return value — XLA then
        dead-code-eliminates their output materialization, so the group
        only pays (device buffers + host jax.Array wrapping) for outputs
        somebody consumes. Flags are the stable part of the dark-sink test
        (receivers/taps/WAL/redirect/statistics), so a staged-row blip
        never forces a retrace; a flag flip (callback attached mid-run)
        rebuilds the jit once — one tracked compile."""
        flags = []
        for m in self.members:
            j = m.output_junction
            observable = (bool(m.callbacks) or m.table_executor is not None
                          or j is None or not _sink_dark(j))
            flags.append(observable)
        return tuple(flags)

    def _make_jit(self, emit_flags: tuple):
        stats = self.ctx.statistics
        gname = self.name
        steps = self._steps

        def fused(states, batch, now):
            # one compile per (group, shape) — vs one per (member, shape).
            # outs is COMPACT (emitting members only, source order): a None
            # placeholder in the traced output pytree would knock every
            # call off pjit's C++ fastpath onto the slow python path
            stats.track_compile(gname, batch.capacity)
            new_states, outs = [], []
            for st, step, emit in zip(states, steps, emit_flags):
                s2, out = step(st, batch, now, None)
                new_states.append(s2)
                if emit:
                    outs.append(out)
            return tuple(new_states), tuple(outs)

        return jax.jit(fused, donate_argnums=(0,))

    # ------------------------------------------------------------- dispatch

    def on_batch(self, batch: EventBatch, now: int) -> None:
        debugger = getattr(self.ctx, "debugger", None)
        if debugger is not None:
            # per-query breakpoints need per-query dispatch: fall back to
            # each member's own step (identical math, separate compiles)
            for m in self.members:
                m.on_batch(batch, now)
            return
        t0 = time.perf_counter_ns()
        if batch.capacity < self._batch_cap and not self._bucket_ok:
            batch = batch.pad_to(self._batch_cap)
        flags = self._current_emit_flags()
        if flags != self._emit_flags:
            # a sink lit up (callback/subscriber attached) or went dark:
            # rebuild the jit so the traced return value matches — costs
            # one retrace, visible in the compile counters
            self._emit_flags = flags
            self._step = self._make_jit(flags)
        states = tuple(m.state for m in self.members)
        traced = self.ctx.statistics.compiles.get(self.name, 0)
        new_states, outs = self._step(states, batch, jnp.int64(now))
        traced = self.ctx.statistics.compiles.get(self.name, 0) != traced
        # write ALL states back before any distribution: a member's output
        # cascade can re-enter this junction (and this group) synchronously
        for m, s in zip(self.members, new_states):
            m.state = s
        elapsed = time.perf_counter_ns() - t0
        share = elapsed // len(self.members)
        stats = self.ctx.statistics
        meter = getattr(self.ctx, "tenant_meter", None)
        if meter is not None:
            # equal-share attribution, same split as stats/telemetry below
            meter.record_block(self._member_names, share)
        tele = getattr(self.ctx, "telemetry", None)
        outs_it = iter(outs)
        stats_on = stats.detail
        for m, emit in zip(self.members, flags):
            if emit:
                m._distribute(next(outs_it), now)
            # per-query attribution survives fusion: each member reports an
            # equal share of the fused step's wall time
            if stats_on:
                stats.track_latency(m.name, share)
            m._post_step_maintenance()
        if tele is not None and tele.on:
            cells = self._tele_cells
            if cells is None:
                cells = self._tele_cells = [
                    tele.query_cell(n) for n in self._member_names]
            tele.record_query_block(cells, self._member_names, share,
                                    compiled=traced)
        stats.track_latency(self.name, elapsed)
        self._batches_seen += 1

    # -------------------------------------------------------------- warmup

    def warmup(self, buckets=None) -> int:
        """AOT-compile the fused step per lane bucket (see
        QueryRuntime.warmup / aot_warm — compile-only, no execution, no
        state mutation). Returns fresh compiles under the group's name."""
        if buckets is None:
            buckets = (dtypes.bucket_ladder(self._batch_cap)
                       if self._bucket_ok and dtypes.config.shape_buckets
                       and self.ctx.mesh is None else (self._batch_cap,))
        flags = self._current_emit_flags()
        if flags != self._emit_flags:
            self._emit_flags = flags
            self._step = self._make_jit(flags)
        n0 = self.ctx.statistics.compiles.get(self.name, 0)
        now = jnp.int64(self.ctx.timestamp_generator.current_time())
        states = tuple(m.state for m in self.members)
        for cap in buckets:
            batch = EventBatch.empty(self.junction.definition, cap)
            aot_warm(self._step, states, batch, now)
        return self.ctx.statistics.compiles.get(self.name, 0) - n0

    # -------------------------------------------------------------- splice
    #
    # One-retrace membership change: the dark-sink re-light mechanism
    # above (emit-flag flip -> _make_jit once) generalized to the member
    # list itself. `_make_jit` reads `self._steps` when BUILDING the jit,
    # so every splice REBINDS members/_steps/_member_names to fresh lists
    # — the pre-splice jit keeps closing over the old list object and
    # stays valid, which is what makes rollback a pure attribute restore.
    # Sibling state tensors need no migration: states are assembled from
    # `m.state` per dispatch and written back per member, so the unfused
    # layout IS the fused layout (same property snapshots/upgrades rely
    # on). The retrace covers exactly one compile; departing members are
    # dead-code-eliminated the same way dark sinks are.

    def splice_decline(self, qr) -> Optional[str]:
        """Why `qr` cannot splice into THIS group (None = spliceable).
        Extends runtime_decline with the group-shape facts."""
        reason = runtime_decline(qr)
        if reason is not None:
            return reason
        if qr._batch_cap != self._batch_cap:
            return SPLICE_DECLINE_SHAPE
        if len(self.members) >= group_cap():
            return SPLICE_DECLINE_CAP
        return None

    def splice_in(self, qr: QueryRuntime) -> float:
        """Trace `qr` into the group: siblings' step bodies unchanged,
        their state tensors carried over untouched, ONE retrace eagerly
        compiled before return (deploy pays the compile, not traffic).
        Transactional — any failure restores the exact pre-splice
        bindings and re-raises. Returns wall milliseconds spent."""
        snap = (self.members, self._steps, self._member_names,
                self._emit_flags, self._step, self._bucket_ok,
                self.has_time_semantics, self._tele_cells)
        t0 = time.perf_counter_ns()
        try:
            _apply_pushdown(qr)
            self.members = self.members + [qr]
            self._steps = self._steps + [
                qr._make_step(track_compiles=False)]
            self._member_names = self._member_names + [qr.name]
            self._bucket_ok = self._bucket_ok and qr._bucket_ok
            self.has_time_semantics = (self.has_time_semantics
                                       or qr.has_time_semantics)
            self._emit_flags = self._current_emit_flags()
            self._step = self._splice_commit(self._emit_flags)
            self._tele_cells = None
            qr._fused_group = self
        except BaseException:
            (self.members, self._steps, self._member_names,
             self._emit_flags, self._step, self._bucket_ok,
             self.has_time_semantics, self._tele_cells) = snap
            qr._fused_group = None
            raise
        return (time.perf_counter_ns() - t0) / 1e6

    def splice_out(self, qr: QueryRuntime) -> float:
        """Remove `qr` from the group with siblings undisturbed: the
        departing member's step body drops out of the fused return value
        and XLA DCEs it on the (single) retrace. The caller dissolves
        instead when membership would fall below 2. Returns wall ms."""
        idx = self.members.index(qr)
        assert len(self.members) > 2, "dissolve() below 2 members"
        snap = (self.members, self._steps, self._member_names,
                self._emit_flags, self._step, self._bucket_ok,
                self.has_time_semantics, self._tele_cells)
        t0 = time.perf_counter_ns()
        try:
            self.members = self.members[:idx] + self.members[idx + 1:]
            self._steps = self._steps[:idx] + self._steps[idx + 1:]
            self._member_names = (self._member_names[:idx]
                                  + self._member_names[idx + 1:])
            self._bucket_ok = all(m._bucket_ok for m in self.members)
            self.has_time_semantics = any(m.has_time_semantics
                                          for m in self.members)
            self._emit_flags = self._current_emit_flags()
            self._step = self._splice_commit(self._emit_flags)
            self._tele_cells = None
            qr._fused_group = None
        except BaseException:
            (self.members, self._steps, self._member_names,
             self._emit_flags, self._step, self._bucket_ok,
             self.has_time_semantics, self._tele_cells) = snap
            qr._fused_group = self
            raise
        return (time.perf_counter_ns() - t0) / 1e6

    def dissolve(self) -> list:
        """Unfuse every member (group shrank below 2, or a full rebuild
        was requested). Members keep their own steps/state — the caller
        re-inserts them into the junction's receiver slot in order."""
        members = list(self.members)
        for m in members:
            m._fused_group = None
        return members

    def _splice_commit(self, emit_flags: tuple):
        """Build the post-splice jit and eagerly compile it at the group's
        traced capacity, so the one retrace lands inside deploy latency
        instead of stalling the next traffic batch. (Smaller warmed
        buckets of the pre-splice jit recompile lazily if the group is
        bucket-eligible — full-capacity traffic never stalls.)

        The warm is an actual EXECUTION on an empty batch, not just
        lower().compile(): PR 18 measured (XLA:CPU) a lower-only warm
        leaving the first traffic batch ~100s of ms slow — the cliff the
        splice exists to avoid. That is first-dispatch cost, not a second
        backend compile: on jax 0.9.0 a query step called after
        lower().compile() compiles nothing more, on the CPU and on the TPU
        v5e alike (PR 21, chip_smoke.py `first_batch_after_warmup`); which
        warm primitive to keep is ROADMAP D6. The step is pure and the
        batch empty, so the run has no observable effect; states are
        deep-copied first because donate_argnums=(0,) would otherwise
        invalidate the live member state buffers.

        A separate method so fault injection (util.faults.inject) can
        fail a splice mid-flight; splice_in/splice_out roll back to the
        pre-splice bindings on any exception raised here."""
        step = self._make_jit(emit_flags)
        now = jnp.int64(self.ctx.timestamp_generator.current_time())
        states = jax.tree_util.tree_map(
            jnp.array, tuple(m.state for m in self.members))
        batch = EventBatch.empty(self.junction.definition, self._batch_cap)
        jax.block_until_ready(step(states, batch, now))
        return step


# ---------------------------------------------------------------- formation


def build_shared_groups(rt) -> dict:
    """Form shared groups on a freshly built SiddhiAppRuntime. Mutates
    junction receiver lists (contiguous-run splice) and per-member filter
    lists (pushdown); returns the runtime optimizer report dict stored as
    rt.optimizer_report and surfaced by statistics_report()["optimizer"].

    MUST run before start()/warmup() and before any traffic: the fused jit
    re-traces member step bodies, and pushdown mutates the captured filter
    lists — both are only safe while every step is still cold."""
    static = analyze_sharing(rt.app, enabled=True)
    groups: list[SharedStepGroup] = []
    # statically-decided declines (partitions, OBJECT streams, ...) carry
    # over even for queries that never appear as junction receivers here
    # (partition inner queries route through per-key runtimes)
    declined: dict[str, str] = dict(static.declined)
    pushdowns = 0

    # every junction that can host QueryRuntime receivers: app streams,
    # fault streams, trigger streams, named-window emissions
    seen: set[int] = set()
    junctions = list(rt.junctions.values())
    junctions += list(rt.fault_junctions.values())
    junctions += [w.output_junction for w in rt.windows.values()
                  if getattr(w, "output_junction", None) is not None]

    for junction in junctions:
        if id(junction) in seen:
            continue
        seen.add(id(junction))
        receivers = junction.receivers
        qrs_here = [r for r in receivers if isinstance(r, QueryRuntime)]
        # runs of (index, member) with identical dispatch shape
        i, out, seq = 0, [], 0
        while i < len(receivers):
            r = receivers[i]
            reason = runtime_decline(r) if isinstance(r, QueryRuntime) \
                else DECLINE_JOIN_PATTERN
            if not isinstance(r, QueryRuntime):
                out.append(r)
                i += 1
                continue
            if reason is not None:
                if len(qrs_here) >= 2:
                    declined[r.name] = reason
                out.append(r)
                i += 1
                continue
            # members only need the same traced capacity; mixed _bucket_ok
            # is fine — the group pads to full capacity when ANY member is
            # shape-baked (exactly what that member's own on_batch does)
            key = r._batch_cap
            run = [r]
            j = i + 1
            while j < len(receivers):
                nxt = receivers[j]
                if (not isinstance(nxt, QueryRuntime)
                        or runtime_decline(nxt) is not None
                        or nxt._batch_cap != key):
                    break
                run.append(nxt)
                j += 1
            if len(run) >= 2:
                # chunk long runs at the group cap: compile count stays
                # O(run/cap) — sublinear — while each fused graph stays
                # small enough for XLA to compile and schedule well
                cap = group_cap()
                for k in range(0, len(run), cap):
                    chunk = run[k:k + cap]
                    if len(chunk) < 2:
                        out.extend(chunk)
                        continue
                    for m in chunk:
                        pushdowns += _apply_pushdown(m)
                    seq += 1
                    group = SharedStepGroup(
                        f"shared:{junction.definition.id}:{seq}", chunk,
                        junction)
                    groups.append(group)
                    out.append(group)
            else:
                out.extend(run)
            i = j
        junction.receivers[:] = out

    rt.shared_groups = groups
    report = {
        "enabled": True,
        "groups": len(groups),
        "queries_fused": sum(len(g.members) for g in groups),
        "group_members": {g.name: [m.name for m in g.members]
                          for g in groups},
        # static-analysis counts: what the one traced computation shares
        # (XLA CSE realizes these inside the fused executable)
        "cse_hits": static.cse_hits,
        "pane_candidates": static.pane_candidates,
        "pushdowns": pushdowns,
        "declined": declined,
    }
    rt.optimizer_report = report
    return report
