"""Device-resident supersteps: K ingress batches per device dispatch.

The async ingress feeder (core/ingress.py) normally delivers one full ring
chunk per controller-lock acquisition: one pjit dispatch per query (or
fused group) per micro-batch, plus the host fan-out. At CPU/TPU dispatch
cost ~0.1-6 ms that per-batch hop dominates the stateful laggards long
before the kernels do.

A superstep amortizes the hop: the feeder stages K consecutive full chunks
into one `[K, B]` host block, uploads it with a single device_put, and the
WHOLE eligible query chain — every runtime reachable from the ingress
junction through scannable-through junctions — runs as one `lax.scan` over
the K leading axis with the per-query state tuple as the donated carry.
One dispatch per K batches instead of (nodes x K).

Outputs stay per-batch observable:

  * inside the scan each emitting node's published form
    (`_select_event_type`) is collected per iteration;
  * after the scan, one on-device compaction per emitting slot — per-slot
    valid counts + a single `stable_partition_order` gather over the
    flattened `[K*W]` lanes — packs every valid row, in (iteration, lane)
    order, into a dense prefix;
  * ONE device_get fetches counts + dense buffers, and a host replay loop
    re-publishes slice k to the node's output junction exactly where the
    K=1 path would have (`publish_batch` → `_deliver`), so sinks,
    callbacks on terminal streams, ineligible downstream queries, rate
    limiters (scanned in-state) and telemetry all see per-batch semantics.
    Row content is bit-identical to K=1: compaction preserves lane order
    and `to_host_events`/window masks never read invalid lanes.

Telemetry: the feeder mints one BatchTrace per inner batch from the
per-slot staging t0s; the replay pushes each trace, replays the chain
junction spans nested exactly as `_deliver` would, and attributes each
query an equal share of the measured scan wall time — traces stay per
inner batch and stage spans stay additive (docs/OBSERVABILITY.md).

Eligibility is decided once (lazily, at the first staged superstep) by a
walk from the ingress junction and revalidated cheaply per dispatch;
ineligible plans decline LOUDLY (one log line + statistics_report entry +
the static SL506 lint) and fall back to the K=1 path forever. The knob is
`@app:superstep(k=)` / env SIDDHI_SUPERSTEP_K (core/app_runtime.py).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.search import stable_partition_order
from ..query_api.execution import OutputAction
from .event import EventBatch

# ----------------------------------------------------------- decline taxonomy
#: surfaced verbatim in the feeder log line, statistics_report()
#: ["superstep"], and mirrored by the static SL506 lint (analysis/rules.py)
DECLINE_RECEIVER = "receiver is not a scannable query/join/shared-group"
DECLINE_BREAKER = "query has a circuit breaker"
DECLINE_FAULT = "fault-stream query"
DECLINE_OBJECT = "OBJECT-typed attributes have no scannable layout"
DECLINE_TABLE = "table dependency or input fallback"
DECLINE_CALLBACK = "query callbacks attached"
DECLINE_HOST_SLOT = "host uuid()/unionSet() selector slots"
DECLINE_ACTION = "non-INSERT output action (table executor)"
DECLINE_PARTITION = "partitioned query"
DECLINE_JOIN_BUILD = "join build side is a table/named-window/aggregation"
DECLINE_JOIN_TRIGGER = "join side does not trigger output"
DECLINE_JUNCTION = "junction has taps/event-time gate/redirect/error handler/WAL"
DECLINE_FAN_IN = "fan-in: junction fed by multiple scanned producers"
DECLINE_PLAYBACK = "playback clock advances per delivery"
DECLINE_EMPTY = "no receivers on the async stream"


class _Decline(Exception):
    def __init__(self, reason: str) -> None:
        super().__init__(reason)
        self.reason = reason


class _Node:
    """One scanned step: a plain QueryRuntime, a SharedStepGroup, or one
    triggering join side. `parent` is the node index whose published output
    feeds this node (-1 = the ingress chunk itself)."""

    __slots__ = ("kind", "qr", "name", "parent", "children", "cap",
                 "pad_always", "bucket_ok", "etype", "out_junction",
                 "members", "from_left")

    def __init__(self, kind: str, qr, name: str, parent: int, cap: int,
                 bucket_ok: bool, etype, out_junction,
                 pad_always: bool = False, members=None,
                 from_left: bool = False) -> None:
        self.kind = kind
        self.qr = qr
        self.name = name
        self.parent = parent
        self.children: list[int] = []
        self.cap = cap
        self.pad_always = pad_always
        self.bucket_ok = bucket_ok
        self.etype = etype
        self.out_junction = out_junction
        self.members = members or []
        self.from_left = from_left


# ------------------------------------------------------------ eligibility


def _query_decline(qr) -> Optional[str]:
    """Why this QueryRuntime cannot be scanned (None = eligible). A strict
    superset of shared.runtime_decline minus custom aggregates: the
    compaction cadence (`_post_step_maintenance`) is replayed per inner
    batch after state writeback, so distinctCount tables keep their
    compaction schedule."""
    from ..query_api.definition import AttributeType
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
    if qr.callbacks:
        return DECLINE_CALLBACK
    if qr.selector.host_uuid_slots or \
            getattr(qr.selector, "host_set_slots", None):
        return DECLINE_HOST_SLOT
    if qr.query.output_stream.action != OutputAction.INSERT or \
            qr.table_executor is not None:
        return DECLINE_ACTION
    return None


def _join_decline(r) -> Optional[str]:
    """Why this _JoinSideReceiver cannot be scanned. Only stream-stream
    joins whose scanned side triggers output are eligible: the build side's
    state rides in the carried 5-tuple, while table/named-window/
    aggregation builds live outside it and can be mutated host-side between
    inner batches on the K=1 path."""
    from ..query_api.definition import AttributeType
    qr = r.runtime
    side = qr.left if r.from_left else qr.right
    build = qr.right if r.from_left else qr.left
    from ..query_api.execution import EventTrigger
    triggers = (qr.trigger == EventTrigger.ALL
                or (qr.trigger == EventTrigger.LEFT and r.from_left)
                or (qr.trigger == EventTrigger.RIGHT and not r.from_left))
    if not triggers:
        return DECLINE_JOIN_TRIGGER
    if build.is_table or build.is_named_window or build.is_aggregation:
        return DECLINE_JOIN_BUILD
    if getattr(qr, "breaker", None) is not None:
        return DECLINE_BREAKER
    if qr.callbacks:
        return DECLINE_CALLBACK
    if qr.selector.host_uuid_slots or \
            getattr(qr.selector, "host_set_slots", None):
        return DECLINE_HOST_SLOT
    if qr.query.output_stream.action != OutputAction.INSERT or \
            qr.table_executor is not None:
        return DECLINE_ACTION
    if side.junction is not None and any(
            a.type == AttributeType.OBJECT
            for a in side.junction.definition.attributes):
        return DECLINE_OBJECT
    return None


def _junction_decline(j) -> Optional[str]:
    if j.taps or j._et is not None or j._redirect is not None \
            or j.wal is not None or j.on_error is not None \
            or j.on_error_action is not None:
        return DECLINE_JUNCTION
    return None


class SuperstepRunner:
    """One runner per async ingress junction, built lazily by the feeder at
    the first staged superstep. `dispatch(slots)` returns False when this
    superstep must fall back to per-batch delivery (debugger attached, plan
    invalidated by a topology change); the feeder then delivers the staged
    chunks through the ordinary K=1 path."""

    def __init__(self, pipeline, k: int) -> None:
        self.pipeline = pipeline
        self.j = pipeline.j
        self.ctx = pipeline.ctx
        self.k = int(k)
        self.name = f"superstep:{self.j.definition.id}"
        self.B = self.j.batch_size
        self.nodes: list[_Node] = []
        self.roots: list[int] = []
        self._steps: list = []          # per node: fn | [member fns]
        self._build_plan()
        # receiver-list snapshots for cheap per-dispatch revalidation: a
        # subscribe/unsubscribe anywhere in the scanned region rebuilds
        self._junctions = [self.j] + [n.out_junction for n in self.nodes
                                      if n.children]
        self._snaps = [tuple(id(r) for r in j.receivers)
                       for j in self._junctions]
        self._n_queries = sum(len(n.members) if n.kind == "group" else 1
                              for n in self.nodes)
        self._emit_flags = self._current_emit_flags()
        self._emit_slots: list = []     # (node_idx, member_idx|None)
        self._fn = self._make_jit(self._emit_flags)
        self._tele_cells: dict = {}
        self._warmed = False

    # ------------------------------------------------------------ plan build

    def _build_plan(self) -> None:
        from .query_runtime import QueryRuntime
        ctx = self.ctx
        if ctx.playback:
            raise _Decline(DECLINE_PLAYBACK)
        if not self.j.receivers:
            raise _Decline(DECLINE_EMPTY)
        why = _junction_decline(self.j)
        if why:
            raise _Decline(why)
        claimed = {id(self.j)}
        self._add_receivers(self.j, -1, claimed, require=True)
        if not self.nodes:
            raise _Decline(DECLINE_EMPTY)
        self.roots = [i for i, n in enumerate(self.nodes) if n.parent < 0]

    def _add_receivers(self, j, parent: int, claimed: set,
                       require: bool) -> bool:
        """Try to scan every receiver of `j`. With require=True (the
        ingress junction) any ineligible receiver declines the whole plan;
        with require=False (a chain junction) the caller keeps the parent
        terminal instead. Fan-in onto an already-claimed junction always
        declines: replayed host deliveries would reorder against in-scan
        consumption."""
        from .join_runtime import _JoinSideReceiver
        from .query_runtime import QueryRuntime
        from .shared import SharedStepGroup
        mark = len(self.nodes)
        added: list[int] = []
        try:
            for r in list(j.receivers):
                if type(r) is QueryRuntime:
                    why = _query_decline(r)
                    if why:
                        raise _Decline(f"{r.name}: {why}")
                    node = _Node("query", r, r.name, parent, r._batch_cap,
                                 r._bucket_ok,
                                 r.query.output_stream.event_type,
                                 r.output_junction)
                    self._steps.append(r._make_step(track_compiles=False))
                elif isinstance(r, SharedStepGroup):
                    for m in r.members:
                        why = _query_decline(m)
                        if why:
                            raise _Decline(f"{m.name}: {why}")
                    node = _Node("group", r, r.name, parent, r._batch_cap,
                                 r._bucket_ok, None, None, members=r.members)
                    self._steps.append(list(r._steps))
                elif isinstance(r, _JoinSideReceiver):
                    why = _join_decline(r)
                    if why:
                        raise _Decline(f"{r.runtime.name}: {why}")
                    qr = r.runtime
                    side = qr.left if r.from_left else qr.right
                    node = _Node("join", qr, qr.name, parent,
                                 side.junction.batch_size, False,
                                 qr.query.output_stream.event_type,
                                 qr.output_junction, pad_always=True,
                                 from_left=r.from_left)
                    self._steps.append(qr._make_step(from_left=r.from_left))
                else:
                    raise _Decline(
                        f"{type(r).__name__}: {DECLINE_RECEIVER}")
                self.nodes.append(node)
                idx = len(self.nodes) - 1
                added.append(idx)
                if parent >= 0:
                    self.nodes[parent].children.append(idx)
            # recurse: scan through each added node's output junction when
            # every one of ITS receivers is eligible too
            for idx in added:
                node = self.nodes[idx]
                if node.kind == "group":
                    continue  # member outputs deliver terminally
                oj = node.out_junction
                if oj is None or not oj.receivers:
                    continue
                if id(oj) in claimed:
                    raise _Decline(DECLINE_FAN_IN)
                if _junction_decline(oj):
                    continue  # terminal: replay delivers through _deliver
                claimed.add(id(oj))
                if not self._add_receivers(oj, idx, claimed, require=False):
                    claimed.discard(id(oj))
            return True
        except _Decline as d:
            if require or d.reason == DECLINE_FAN_IN:
                # fan-in always declines the WHOLE plan: treating the
                # second producer as terminal would deliver its batches
                # after the scan consumed the first producer's K batches —
                # reordered relative to the K=1 interleaving
                raise
            # roll back this junction's children; the parent goes terminal
            del self._steps[mark:]
            del self.nodes[mark:]
            if parent >= 0:
                self.nodes[parent].children = [
                    c for c in self.nodes[parent].children if c < mark]
            return False

    # ------------------------------------------------------------- emit flags

    def _current_emit_flags(self) -> tuple:
        """Per node: is the terminal output observable? Mirrors
        shared.SharedStepGroup._current_emit_flags — scanned-through nodes
        (children consume the output in-scan) never deliver terminally.
        Group entries are per-member tuples."""
        from .query_runtime import _sink_dark
        flags = []
        for n in self.nodes:
            if n.kind == "group":
                flags.append(n.qr._current_emit_flags())
            elif n.children:
                flags.append(False)
            else:
                j = n.out_junction
                flags.append(j is not None and not _sink_dark(j))
        return tuple(flags)

    # -------------------------------------------------------------- the scan

    def _make_jit(self, emit_flags: tuple):
        from .query_runtime import QueryRuntime
        nodes = self.nodes
        steps = self._steps
        stats = self.ctx.statistics
        name = self.name
        B = self.B
        emit_slots: list = []
        for i, n in enumerate(nodes):
            if n.kind == "group":
                emit_slots.extend((i, mi) for mi, f in enumerate(emit_flags[i])
                                  if f)
            elif emit_flags[i]:
                emit_slots.append((i, None))
        self._emit_slots = emit_slots
        chain_nodes = [i for i, n in enumerate(nodes) if n.children]
        self._chain_nodes = chain_nodes

        def pad_in(inp, node):
            if inp.capacity < node.cap and (node.pad_always
                                            or not node.bucket_ok):
                return inp.pad_to(node.cap)
            return inp

        def superstep(states, ts_k, cols_k, now_k):
            # one compile per runner (full chunks only: shapes never vary)
            stats.track_compile(name, ts_k.shape[1])

            def body(carry, x):
                sts, drops = list(carry[0]), list(carry[1])
                ts, cols, now = x
                ingress = EventBatch(
                    ts=ts, cols=cols,
                    valid=jnp.ones((B,), jnp.bool_),
                    types=jnp.zeros((B,), jnp.int8))
                fwds: dict = {}
                emits: dict = {}
                counts: dict = {}
                for i, node in enumerate(nodes):
                    inp = ingress if node.parent < 0 else fwds[node.parent]
                    inp = pad_in(inp, node)
                    if node.kind == "group":
                        new_sts = []
                        for mi, (st, stp, m) in enumerate(
                                zip(sts[i], steps[i], node.members)):
                            s2, out = stp(st, inp, now, None)
                            new_sts.append(s2)
                            if emit_flags[i][mi]:
                                f = QueryRuntime._select_event_type(
                                    out, m.query.output_stream.event_type)
                                emits[(i, mi)] = (f.ts, f.cols, f.valid)
                        sts[i] = tuple(new_sts)
                        continue
                    if node.kind == "join":
                        s2, out, dropped = steps[i](sts[i], inp, now, None)
                        drops[i] = drops[i] + dropped
                    else:
                        s2, out = steps[i](sts[i], inp, now,
                                           node.qr._table_states())
                    sts[i] = s2
                    if node.children or emit_flags[i]:
                        fwd = QueryRuntime._select_event_type(out, node.etype)
                        if node.children:
                            fwds[i] = fwd
                            counts[i] = jnp.sum(fwd.valid.astype(jnp.int32))
                        else:
                            emits[(i, None)] = (fwd.ts, fwd.cols, fwd.valid)
                ys = (tuple(emits[s] for s in emit_slots),
                      tuple(counts[i] for i in chain_nodes))
                return (tuple(sts), tuple(drops)), ys

            drops0 = tuple(jnp.int32(0) for _ in nodes)
            (states2, drops2), (ys_emit, ys_counts) = jax.lax.scan(
                body, (states, drops0), (ts_k, cols_k, now_k))
            # on-device compaction: one stable partition per emitting slot
            # packs every valid row — in (iteration, lane) order — into a
            # dense prefix of the flattened [K*W] buffer, so slice k of the
            # SINGLE fetched array is exactly inner batch k's output
            compacted = []
            for ts_y, cols_y, valid_y in ys_emit:
                cnt = jnp.sum(valid_y.astype(jnp.int32), axis=1)
                perm = stable_partition_order(valid_y.reshape(-1))
                compacted.append(
                    (cnt, ts_y.reshape(-1)[perm],
                     {a: v.reshape(-1)[perm] for a, v in cols_y.items()}))
            return states2, tuple(compacted), ys_counts, drops2

        return jax.jit(superstep, donate_argnums=(0,))

    def warm(self) -> None:
        """AOT-compile the superstep (query_runtime.aot_warm) so the first
        dispatch never pays the trace+compile inside the controller lock."""
        if self._warmed:
            return
        from .query_runtime import aot_warm
        K, B = self.k, self.B
        ts_k = np.zeros((K, B), np.int64)
        cols_k = {a: np.zeros((K, B), dt)
                  for a, dt in zip(self.pipeline.attrs,
                                   self.pipeline.np_dtypes)}
        now_k = np.zeros((K,), np.int64)
        aot_warm(self._fn, self._states(), ts_k, cols_k, now_k)
        self._warmed = True

    def _states(self) -> tuple:
        return tuple(tuple(m.state for m in n.members)
                     if n.kind == "group" else n.qr.state
                     for n in self.nodes)

    # -------------------------------------------------------------- dispatch

    def revalidate(self) -> bool:
        """Cheap per-dispatch guard: the scanned topology (receiver lists,
        callbacks, debugger) must still match the built plan. False = the
        caller must fall back (and rebuild on the next superstep)."""
        if getattr(self.ctx, "debugger", None) is not None:
            return False
        for j, snap in zip(self._junctions, self._snaps):
            if tuple(id(r) for r in j.receivers) != snap:
                return False
        for n in self.nodes:
            qrs = n.members if n.kind == "group" else [n.qr]
            for qr in qrs:
                if qr.callbacks or qr.selector.host_uuid_slots:
                    return False
        return True

    def dispatch(self, slots: list) -> bool:
        """Run one superstep over `slots` = [(ts_buf, col_bufs, t0_ns), ...]
        (feeder thread, controller lock NOT held). Returns False when the
        caller must deliver the slots through the K=1 path instead."""
        if not self.revalidate():
            return False
        flags = self._current_emit_flags()
        if flags != self._emit_flags:
            # a terminal sink lit up or went dark: one retrace, mirrored
            # from shared.SharedStepGroup.on_batch
            self._emit_flags = flags
            self._fn = self._make_jit(flags)
            self._warmed = False
        pipe = self.pipeline
        ctx = self.ctx
        j = self.j
        K = len(slots)
        tele = getattr(ctx, "telemetry", None)
        tracing = tele is not None and tele.on
        sid = j.definition.id

        # ---- one host stack + one device_put for the whole superstep ----
        t0 = time.perf_counter_ns()
        ts_k = jnp.asarray(np.stack([s[0] for s in slots]))
        cols_k = {a: jnp.asarray(np.stack([s[1][ai] for s in slots]))
                  for ai, a in enumerate(pipe.attrs)}
        h2d = time.perf_counter_ns() - t0
        pipe.cells.book("h2d", h2d, units=K)
        traces = None
        if tracing:
            traces = []
            for ts_buf, _cols, slot_t0 in slots:
                tr = tele.mint(sid, self.B, t0=slot_t0)
                tr.h2d_ns = h2d // K
                tr.superstep = K
                traces.append(tr)
                tele.record_lag(sid, int(ts_buf[-1]))

        with ctx.controller_lock:
            # staged (sync-path) rows flush first: arrival order, exactly
            # as _deliver_locked / publish_batch would
            for cj in self._junctions:
                if cj._staged_rows or cj._tap_queue:
                    cj.flush()
            now = ctx.timestamp_generator.current_time()
            now_k = jnp.full((K,), now, jnp.int64)
            d0 = time.perf_counter_ns()
            states2, compacted, chain_counts, drops = self._fn(
                self._states(), ts_k, cols_k, now_k)
            # ONE fetch per superstep: counts + dense compacted outputs
            host = jax.device_get(compacted)
            chain_host = jax.device_get(chain_counts) if chain_counts else ()
            scan_ns = time.perf_counter_ns() - d0
            pipe._ss_scan_ns += scan_ns
            # write every state back BEFORE any distribution: terminal
            # callbacks can re-enter the ingress junction synchronously
            for n, s in zip(self.nodes, states2):
                if n.kind == "group":
                    for m, ms in zip(n.members, s):
                        m.state = ms
                else:
                    n.qr.state = s
            try:
                self._replay(slots, host, chain_host, drops, traces, now,
                             d0, scan_ns)
            except Exception as e:
                # the scan already COMMITTED (states written back): the
                # slots must not be re-delivered through the K=1 path, or
                # every window/aggregate would double-count them. Mark the
                # error as committed so the feeder disables supersteps
                # without replaying, and keep the feeder thread alive.
                e.superstep_committed = True  # type: ignore[attr-defined]
                raise
            dev = time.perf_counter_ns() - d0
            pipe._ss_replay_ns += dev - scan_ns
            pipe.cells.book("device", dev, units=K)
            pipe._batches += K
        return True

    # ---------------------------------------------------------------- replay

    def _replay(self, slots, host, chain_host, drops, traces, now: int,
                d0: int, scan_ns: int) -> None:
        """Per-inner-batch host fan-out: replay counters, traces, terminal
        publishes, and per-query maintenance in the exact nesting order of
        K single-batch deliveries."""
        ctx = self.ctx
        stats = ctx.statistics
        tele = getattr(ctx, "telemetry", None)
        tracing = traces is not None
        K = len(slots)
        sid = self.j.definition.id
        # equal-share attribution, like SharedStepGroup: each query reports
        # scan_wall / (K * queries) so per-trace device spans stay additive
        share = scan_ns // max(K * self._n_queries, 1)
        offsets = [np.zeros(K + 1, np.int64) for _ in host]
        for si, (cnt, _ts, _cols) in enumerate(host):
            offsets[si][1:] = np.cumsum(cnt)
        slot_of = {key: si for si, key in enumerate(self._emit_slots)}
        chain_of = {ni: ci for ci, ni in enumerate(self._chain_nodes)}
        flags = self._emit_flags

        def deliver(node, key, k):
            si = slot_of[key]
            cnt, dts, dcols = host[si]
            c = int(cnt[k])
            off = int(offsets[si][k])
            oj = node.out_junction
            # _pad_cap buckets up to the junction batch size, but a step's
            # emit width can exceed it (e.g. a lengthBatch flush emits
            # window-capacity rows): fall back to the slot's device width,
            # which is exactly the width the K=1 step would have delivered
            pcap = oj._pad_cap(c)
            if pcap < c:
                pcap = dts.size // K
            ts_arr = np.zeros(pcap, np.int64)
            cols = {}
            if c:
                ts_arr[:c] = dts[off:off + c]
                ts_arr[c:] = ts_arr[c - 1]  # monotone pad
            for a, v in dcols.items():
                col = np.zeros(pcap, v.dtype)
                if c:
                    col[:c] = v[off:off + c]
                cols[a] = col
            oj.publish_batch(EventBatch.from_numpy(ts_arr, cols, c), now)

        def replay_node(i: int, k: int) -> None:
            node = self.nodes[i]
            if node.kind == "group":
                g = node.qr
                for mi, m in enumerate(node.members):
                    if flags[i][mi]:
                        deliver_member(node, i, mi, k)
                    if stats.detail:
                        stats.track_latency(m.name, share)
                    m._post_step_maintenance()
                if tele is not None and tele.on:
                    cells = self._tele_cells.get(i)
                    if cells is None:
                        cells = self._tele_cells[i] = [
                            tele.query_cell(m.name) for m in node.members]
                    tele.record_query_block(
                        cells, [m.name for m in node.members], share)
                stats.track_latency(g.name, share * len(node.members))
                g._batches_seen += 1
                return
            if flags[i] and not node.children:
                deliver(node, (i, None), k)
            if node.children:
                oj = node.out_junction
                tr2 = None
                if tracing:
                    tr2 = tele.mint(oj.definition.id)
                    tr2.deliver_t0 = time.perf_counter_ns()
                    tele.push_active(tr2)
                ci = chain_of[i]
                n_in = int(chain_host[ci][k]) if stats.enabled else 0
                stats.track_in(oj.definition.id, n_in)
                stats.track_batch(oj.definition.id)
                try:
                    for c in node.children:
                        replay_node(c, k)
                finally:
                    if tr2 is not None:
                        tele.pop_active(tr2)
            if tele is not None and tele.on:
                tele.record_query(node.name, share)
            stats.track_latency(node.name, share)
            if node.kind == "query":
                node.qr._post_step_maintenance()
            else:  # join: replay the device-side drop accounting — the
                # scan already summed this superstep's drops, so the total
                # lands once (k=0) and the warning cadence advances per k
                qr = node.qr
                if k == 0:
                    d = drops[i]
                    qr._dropped_dev = (d if qr._dropped_dev is None
                                       else qr._dropped_dev + d)
                qr._drop_checks += 1
                if not qr._drop_warned and qr._drop_checks % 64 == 0:
                    if int(qr._dropped_dev) > 0:
                        import warnings
                        warnings.warn(
                            f"join {qr.name!r}: "
                            f"{int(qr._dropped_dev)} matched pairs exceeded "
                            "the per-step pair block or the per-probe "
                            "candidate walk and were dropped — raise "
                            "config.join_pair_cap_factor / "
                            "config.join_max_matches", stacklevel=2)
                        qr._drop_warned = True

        def deliver_member(node, i, mi, k):
            m = node.members[mi]
            si = slot_of[(i, mi)]
            cnt, dts, dcols = host[si]
            c = int(cnt[k])
            off = int(offsets[si][k])
            oj = m.output_junction
            if oj is None:
                return
            pcap = oj._pad_cap(c)
            if pcap < c:  # emit wider than the junction bucket: slot width
                pcap = dts.size // K
            ts_arr = np.zeros(pcap, np.int64)
            cols = {}
            if c:
                ts_arr[:c] = dts[off:off + c]
                ts_arr[c:] = ts_arr[c - 1]
            for a, v in dcols.items():
                col = np.zeros(pcap, v.dtype)
                if c:
                    col[:c] = v[off:off + c]
                cols[a] = col
            oj.publish_batch(EventBatch.from_numpy(ts_arr, cols, c), now)

        for k in range(K):
            tr = traces[k] if tracing else None
            if tr is not None:
                tr.deliver_t0 = d0
                tele.push_active(tr)
            try:
                stats.track_in(sid, self.B if stats.enabled else 0)
                stats.track_batch(sid)
                for r in self.roots:
                    replay_node(r, k)
            finally:
                if tr is not None:
                    tele.pop_active(tr)


def build_runner(pipeline, k: int):
    """Feeder entry point: (runner, None) or (None, decline reason)."""
    try:
        runner = SuperstepRunner(pipeline, k)
    except _Decline as d:
        return None, d.reason
    try:
        runner.warm()
    except Exception as e:  # pragma: no cover — lowering failure
        return None, f"superstep compile failed: {e}"
    return runner, None
