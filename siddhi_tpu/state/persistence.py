"""Checkpoint / restore + persistence stores.

Reference: core/util/snapshot/SnapshotService.java:51 walks every StateHolder
under a world-stopping ThreadBarrier, serializes with ByteSerializer, and hands
bytes to a PersistenceStore (core/util/persistence/ — InMemory, FileSystem,
IncrementalFileSystem) keyed by app name + revision
(SiddhiAppRuntimeImpl.persist:686, SiddhiManager.persist:291,
restoreLastRevision:302-320).

Compatibility: a revision restores only into the SAME state layout — a
framework upgrade that changes a runtime's state pytree structure (new
counters, aggregator state redesigns) fails restore LOUDLY with
CannotRestoreStateError rather than silently misassigning leaves; durable
aggregation stores (@store duration tables) are the cross-version path.

TPU design: every runtime's state is a **pytree of device arrays** plus a few
host scalars, so a full snapshot is one `jax.device_get` per runtime — no
barrier needed (execution is single-controller synchronous; there is nothing
in flight between flushes). Revisions are `<ts>_<app>` like the reference's
`<time>_<app>` naming. Serialization is pickle over numpy arrays (the
reference uses Java serialization).
"""

from __future__ import annotations

import os
import pickle
import time
from typing import Optional

import jax
import numpy as np

from ..errors import CannotRestoreStateError


def _to_host(pytree):
    # prestart every device->host copy, then one tree fetch: per-leaf
    # synchronous np.asarray is a blocking device→host round trip EACH
    for leaf in jax.tree_util.tree_leaves(pytree):
        start = getattr(leaf, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # pragma: no cover — prestart is advisory
                break
    return jax.tree_util.tree_map(lambda x: np.asarray(x), pytree)


def _to_device(host_tree, like):
    """Device-put host arrays, casting to the dtypes of the template tree."""
    import jax.numpy as jnp

    # version tolerance: a state NamedTuple that gained a defaulted field
    # (e.g. PatternState.armed0_ts r4, PendingTable.origin r5) unpickles
    # from older snapshots with None in that slot — backfill every
    # None-valued field from the freshly built template of the SAME type
    # (for armed0_ts this re-arms the leading-absent rule at restore time).
    # Recurses because the NamedTuples nest (PatternState holds
    # PendingTables); mismatched types fall through to tree_map's structure
    # error, wrapped by the caller.
    def backfill(h, l):
        if isinstance(h, tuple) and hasattr(h, "_fields") \
                and type(l) is type(h):
            return h._replace(**{
                f: (getattr(l, f) if v is None
                    else backfill(v, getattr(l, f)))
                for f, v in zip(h._fields, h)})
        if isinstance(h, tuple) and type(l) is tuple is type(h) \
                and len(h) == len(l):
            return tuple(backfill(a, b) for a, b in zip(h, l))
        return h

    host_tree = backfill(host_tree, like)

    def put(h, l):
        arr = jnp.asarray(h)
        if hasattr(l, "dtype") and arr.dtype != l.dtype:
            arr = arr.astype(l.dtype)
        return arr

    return jax.tree_util.tree_map(put, host_tree, like)


class PersistenceStore:
    """SPI (reference: core/util/persistence/PersistenceStore.java)."""

    def save(self, app_name: str, revision: str, snapshot: bytes) -> None:
        raise NotImplementedError

    def load(self, app_name: str, revision: str) -> Optional[bytes]:
        raise NotImplementedError

    def get_last_revision(self, app_name: str) -> Optional[str]:
        raise NotImplementedError

    def clear_all_revisions(self, app_name: str) -> None:
        raise NotImplementedError


class InMemoryPersistenceStore(PersistenceStore):
    """Reference: InMemoryPersistenceStore.java."""

    def __init__(self) -> None:
        self._store: dict[str, dict[str, bytes]] = {}

    def save(self, app_name, revision, snapshot) -> None:
        self._store.setdefault(app_name, {})[revision] = snapshot

    def load(self, app_name, revision):
        return self._store.get(app_name, {}).get(revision)

    def get_last_revision(self, app_name):
        revs = self._store.get(app_name)
        if not revs:
            return None
        return max(revs)  # revisions sort by leading timestamp

    def clear_all_revisions(self, app_name) -> None:
        self._store.pop(app_name, None)


class FileSystemPersistenceStore(PersistenceStore):
    """Reference: FileSystemPersistenceStore.java:33 (save:40, load:89) —
    one file per revision under <base>/<app>/<revision>."""

    def __init__(self, base_dir: str) -> None:
        self.base_dir = base_dir

    def _dir(self, app_name: str) -> str:
        return os.path.join(self.base_dir, app_name)

    def save(self, app_name, revision, snapshot) -> None:
        d = self._dir(app_name)
        os.makedirs(d, exist_ok=True)
        # crash-consistent: fsync the tmp BEFORE the rename (otherwise the
        # rename can land while the data is still page-cache-only and a
        # power cut leaves a whole-looking but torn revision), then fsync
        # the directory so the rename itself is durable. get_last_revision
        # skips dot-prefixed files, so an abandoned tmp is never picked.
        tmp = os.path.join(d, f".{revision}.tmp")
        with open(tmp, "wb") as f:
            f.write(snapshot)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(d, revision))
        try:
            dfd = os.open(d, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:  # pragma: no cover — platform without dir fsync
            pass

    def load(self, app_name, revision):
        path = os.path.join(self._dir(app_name), revision)
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            return f.read()

    def get_last_revision(self, app_name):
        d = self._dir(app_name)
        if not os.path.isdir(d):
            return None
        revs = [f for f in os.listdir(d) if not f.startswith(".")]
        return max(revs) if revs else None

    def clear_all_revisions(self, app_name) -> None:
        d = self._dir(app_name)
        if os.path.isdir(d):
            for f in os.listdir(d):
                os.remove(os.path.join(d, f))


class IncrementalFileSystemPersistenceStore(FileSystemPersistenceStore):
    """Delta persistence (reference: IncrementalFileSystemPersistenceStore.java:37
    + the incremental-snapshot protocol of SnapshotService.java:189).

    The reference collects per-element operation change-logs; here the unit of
    change is the device ARRAY: a revision stores only the pytree leaves whose
    content hash changed since the previous revision. Loading walks back to the
    nearest full snapshot and replays deltas forward. Periodically (every
    `full_every` saves) a full snapshot re-bases the chain so restore cost
    stays bounded. Directory layout / revision naming / atomic writes come
    from FileSystemPersistenceStore; chain order is the lexicographic revision
    order (revisions are strictly-increasing timestamps — SiddhiAppRuntime
    guarantees uniqueness)."""

    def __init__(self, base_dir: str, full_every: int = 16) -> None:
        super().__init__(base_dir)
        self.full_every = full_every
        self._last_hashes: dict[str, dict] = {}  # app -> {path: digest}
        self._saves: dict[str, int] = {}

    @staticmethod
    def _flatten(tree):
        """snapshot → ({path: leaf}, canonical path order, treedef)."""
        with_path, structure = jax.tree_util.tree_flatten_with_path(tree)
        keystr = jax.tree_util.keystr
        flat = {keystr(p): leaf for p, leaf in with_path}
        order = [keystr(p) for p, _ in with_path]
        return flat, order, structure

    @staticmethod
    def _digest(leaf) -> str:
        import hashlib
        h = hashlib.blake2b(digest_size=12)
        if isinstance(leaf, np.ndarray):
            h.update(leaf.tobytes())
            h.update(str(leaf.dtype).encode())
            h.update(str(leaf.shape).encode())
        else:
            h.update(repr(leaf).encode())
        return h.hexdigest()

    def save(self, app_name, revision, snapshot) -> None:
        snap = pickle.loads(snapshot)
        flat, order, structure = self._flatten(snap)
        hashes = {k: self._digest(v) for k, v in flat.items()}
        prev = self._last_hashes.get(app_name)
        n = self._saves.get(app_name, 0)
        full = prev is None or n % self.full_every == 0
        if full:
            payload = {"kind": "full", "leaves": flat}
        else:
            changed = {k: v for k, v in flat.items()
                       if hashes.get(k) != prev.get(k)}
            dropped = [k for k in prev if k not in hashes]
            payload = {"kind": "delta", "leaves": changed, "dropped": dropped}
        # shape + canonical leaf order ride every revision so restore can
        # rebuild the nested snapshot
        payload["structure"] = structure
        payload["order"] = order
        super().save(app_name, revision,
                     pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL))
        self._last_hashes[app_name] = hashes
        self._saves[app_name] = n + 1

    def _read_payload(self, app_name: str, rev: str) -> dict:
        with open(os.path.join(self._dir(app_name), rev), "rb") as f:
            payload = pickle.load(f)
        if not isinstance(payload, dict) or "kind" not in payload:
            raise CannotRestoreStateError(
                f"revision {rev!r} is not an incremental revision (was it "
                "written by a different persistence store?)")
        return payload

    def load(self, app_name, revision):
        d = self._dir(app_name)
        if not os.path.isdir(d):
            return None
        revs = sorted(f for f in os.listdir(d) if not f.startswith("."))
        if revision not in revs:
            return None
        # walk back from `revision` to the nearest full snapshot
        chain = []
        for r in reversed(revs[: revs.index(revision) + 1]):
            payload = self._read_payload(app_name, r)
            chain.append(payload)
            if payload["kind"] == "full":
                break
        if not chain or chain[-1]["kind"] != "full":
            raise CannotRestoreStateError(
                f"no full base found for revision {revision!r} "
                "(older revisions pruned?)")
        leaves: dict = {}
        for payload in reversed(chain):  # base first, then deltas
            for k in payload.get("dropped", ()):
                leaves.pop(k, None)
            leaves.update(payload["leaves"])
        target = chain[0]  # the requested revision carries shape + order
        snap = jax.tree_util.tree_unflatten(
            target["structure"], [leaves[k] for k in target["order"]])
        return pickle.dumps(snap, protocol=pickle.HIGHEST_PROTOCOL)

    def clear_all_revisions(self, app_name) -> None:
        super().clear_all_revisions(app_name)
        self._last_hashes.pop(app_name, None)
        self._saves.pop(app_name, None)


class SnapshotService:
    """Collects/restores all stateful elements of one app runtime
    (reference: SnapshotService.java fullSnapshot:90 / restore:333)."""

    def __init__(self, app_runtime) -> None:
        self.rt = app_runtime
        #: device-delta fetch memo: section key -> (state object, host tree).
        #: Every jitted step REPLACES its state pytree (donated buffers,
        #: functional updates), so object identity is a precise change
        #: detector: `state is cached` means not one batch touched this
        #: runtime since the last snapshot — reuse the cached host copy and
        #: skip the device readback entirely. An idle app persists with
        #: ZERO device->host transfers (the reference's change-log
        #: equivalent, SnapshotableStreamEventQueue.java:44-47, at runtime
        #: granularity).
        self._memo: dict = {}

    def full_snapshot(self) -> bytes:
        rt = self.rt
        rt.flush()  # drain staged rows so the snapshot is a clean cut
        # entries untouched by THIS pass (e.g. @purge-removed partition
        # instances) drop with the memo swap — no per-key host leak
        new_memo: dict = {}

        def fetch(key: str, state):
            hit = self._memo.get(key)
            if hit is not None and hit[0] is state:
                new_memo[key] = hit
                return hit[1]
            host = _to_host(state)
            new_memo[key] = (state, host)
            return host

        snap = {
            "app": rt.app.name,
            "fingerprint": self._fingerprint(),
            "queries": {name: fetch(f"q:{name}", qr.state)
                        for name, qr in rt.query_runtimes.items()
                        if not getattr(qr, "_partitioned", False)},
            # record (@store) tables are external authorities: their rows
            # live in the store, not in device state — skip them (the cache
            # rebuilds from the store/policy on use)
            "tables": {tid: fetch(f"t:{tid}", t.state)
                       for tid, t in rt.tables.items()
                       if not hasattr(t, "store")},
            "windows": {wid: fetch(f"w:{wid}", w.state)
                        for wid, w in getattr(rt, "windows", {}).items()},
            "aggregations": {aid: fetch(f"a:{aid}", a.state)
                             for aid, a in getattr(rt, "aggregations", {}).items()},
            "partitions": {pname: p.snapshot_states(fetch=fetch,
                                                    prefix=f"p:{pname}:")
                           for pname, p in getattr(rt, "partitions", {}).items()},
            "strings": rt.ctx.global_strings.snapshot(),
            "last_event_ts": rt.ctx.timestamp_generator._last_event_ts,
        }
        self._memo = new_memo
        return pickle.dumps(snap, protocol=pickle.HIGHEST_PROTOCOL)

    def _fingerprint(self) -> Optional[str]:
        """App-structure fingerprint stamped into every revision (memoized —
        the app object never changes after creation). Best-effort: a
        lowering failure must never block persist."""
        fp = getattr(self, "_fp_memo", False)
        if fp is False:
            try:
                from ..analysis.plan import plan_fingerprint
                fp = plan_fingerprint(self.rt.app)
            except Exception:  # pragma: no cover — fingerprint is advisory
                fp = None
            self._fp_memo = fp
        return fp

    def restore(self, blob: bytes, *,
                elements: Optional[dict[str, set[str]]] = None) -> None:
        """Restore a snapshot. `elements` (section name -> element-name set)
        limits which stateful sections restore — the state-migratable
        upgrade path feeds it UpgradeDiff.restore_elements(); None restores
        everything (and then a fingerprint mismatch is refused)."""
        rt = self.rt
        try:
            snap = pickle.loads(blob)
        except Exception as e:  # noqa: BLE001
            raise CannotRestoreStateError(str(e)) from e
        if snap.get("app") != rt.app.name:
            raise CannotRestoreStateError(
                f"snapshot belongs to app {snap.get('app')!r}, "
                f"not {rt.app.name!r}")
        # structural gate: refuse a full restore of a snapshot taken under a
        # different app structure instead of corrupting state leaf-by-leaf.
        # Pre-fingerprint snapshots (no stamp) stay loadable; element-mapped
        # restores skip the gate — the caller already diffed the plans.
        snap_fp = snap.get("fingerprint")
        if elements is None and snap_fp is not None:
            own_fp = self._fingerprint()
            if own_fp is not None and snap_fp != own_fp:
                raise CannotRestoreStateError(
                    f"snapshot fingerprint {snap_fp} does not match the "
                    f"current app structure {own_fp} for {rt.app.name!r} — "
                    "the app definition changed since this revision was "
                    "taken; use the upgrade path (element-mapped restore) "
                    "or clear old revisions")

        def wanted(section: str, name: str) -> bool:
            return elements is None or name in elements.get(section, ())

        try:
            for name, qr in rt.query_runtimes.items():
                if name in snap["queries"] and wanted("queries", name) \
                        and not getattr(qr, "_partitioned", False):
                    qr.state = _to_device(snap["queries"][name], qr.state)
            for tid, t in rt.tables.items():
                if tid in snap["tables"] and wanted("tables", tid) \
                        and not hasattr(t, "store"):
                    t.state = _to_device(snap["tables"][tid], t.state)
            for wid, w in getattr(rt, "windows", {}).items():
                if wid in snap.get("windows", {}) and wanted("windows", wid):
                    w.state = _to_device(snap["windows"][wid], w.state)
            for aid, a in getattr(rt, "aggregations", {}).items():
                if aid in snap.get("aggregations", {}) \
                        and wanted("aggregations", aid):
                    a.state = _to_device(snap["aggregations"][aid], a.state)
            for pname, p in getattr(rt, "partitions", {}).items():
                if pname in snap.get("partitions", {}) \
                        and wanted("partitions", pname):
                    p.restore_states(snap["partitions"][pname])
        except (ValueError, KeyError) as e:
            raise CannotRestoreStateError(
                f"snapshot structure mismatch (app definition changed?): {e}"
            ) from e
        rt.ctx.global_strings.restore(snap["strings"])
        if snap.get("last_event_ts") is not None:
            rt.ctx.timestamp_generator._last_event_ts = snap["last_event_ts"]
