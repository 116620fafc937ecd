"""Async in-memory snapshots with peer-redundant shard stores.

Gemini-style (SOSP '23) hot-tier checkpointing for the trainer: on a step
cadence the TrainState is copied device->host WITHOUT blocking the hot
loop, tagged with step + sha256, and stored per *virtual host* (the
in-process emulation of a pod host — ``dtc_tpu.resilience.elastic``).
Recovery from a poisoned update or a lost host then costs at most one
step of lost work, instead of a rollback to the (now cold-tier, slower
cadence) Orbax checkpoint on disk.

Zero-blocking-sync contract (the hostsync lint stays green on the
trainer): :meth:`SnapshotStore.begin` dispatches a DEVICE-side copy of
every leaf (``jnp.copy`` — async dispatch, never a host round-trip; the
copy is what makes the buffers safe against the next step's donation),
starts the device->host transfer with ``copy_to_host_async``, and hands
the copy to a background commit thread. The thread — not the hot loop —
materializes numpy shards, hashes them, and files them into the virtual
hosts' stores. ``begin`` is double-buffered: one commit landing plus one
queued behind it; further cadence ticks are SKIPPED (counted, surfaced
as a ``snapshot`` event field), so a slow commit can never queue
unbounded device copies.

Peer redundancy (computed from the leaf shardings, i.e. from the mesh
axes + rule table — see :func:`RedundancyPlan.from_snapshot`):

- **DP-replicated leaves** — every host's store holds a full copy; any
  one survivor reconstructs them.
- **FSDP-sharded leaves** — each host holds only its own shard, so the
  host's whole shard-set is additionally MIRRORED to its ring neighbor
  ``(h+1) % n_hosts``. Losing host ``h`` is recoverable as long as its
  neighbor survives; :meth:`RedundancyPlan.recovery_set` names the
  minimal surviving host set needed to reconstruct full state (and
  raises :class:`SnapshotIncompleteError` when no such set exists — the
  caller then falls back to the cold tier).

The transport is the same in-process seam the serving fleet's
``EngineReplica`` handles use (dtc_tpu/serve/replica.py): stores are
plain per-host dicts today; a real DCN transport replaces the dict
filing in ``_commit`` without touching the trainer.
"""

from __future__ import annotations

import hashlib
import queue
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from dtc_tpu.resilience.errors import SnapshotIncompleteError

PyTree = Any

#: Per-dimension (start, stop) tuple identifying one shard of a leaf.
ShardKey = tuple


def shard_key(index: tuple, shape: tuple) -> ShardKey:
    """Serialize an ``addressable_shards[i].index`` slice tuple into a
    hashable (start, stop) tuple per dimension (scalars -> ``()``)."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def _sha(arr: np.ndarray) -> str:
    h = hashlib.sha256()
    h.update(str(arr.dtype).encode())
    h.update(str(arr.shape).encode())
    h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


@dataclass
class LeafMeta:
    """Static description of one state leaf, enough to re-shard it onto a
    DIFFERENT mesh: global shape/dtype plus the PartitionSpec its array
    carried (axis NAMES survive a mesh resize; sizes do not)."""

    path: str
    shape: tuple
    dtype: Any
    spec: Any  # jax.sharding.PartitionSpec


@dataclass
class InMemorySnapshot:
    """One committed hot-tier snapshot.

    ``primary[host][path][key]`` holds host ``host``'s own numpy shards;
    ``mirror[host]`` holds the full shard-set of its ring-PREVIOUS host
    (i.e. host ``h``'s shards are mirrored at ``(h+1) % n_hosts``).
    ``shard_sha`` records the commit-time hash of every distinct
    ``(path, key)`` shard — restore re-hashes whichever copy it actually
    uses, so a damaged store (chaos ``lose_snapshot``, bit rot) can never
    silently reconstruct wrong state.
    """

    step: int
    n_hosts: int
    meta: dict = field(default_factory=dict)
    leaves: list[LeafMeta] = field(default_factory=list)
    treedef: Any = None
    primary: dict[int, dict[str, dict[ShardKey, np.ndarray]]] = field(
        default_factory=dict
    )
    mirror: dict[int, dict[str, dict[ShardKey, np.ndarray]]] = field(
        default_factory=dict
    )
    shard_sha: dict[tuple[str, ShardKey], str] = field(default_factory=dict)
    sha256: str = ""
    # False when some leaf's filed shards do not tile its full extent —
    # a commit taken AFTER a host died (its shards could not be stored
    # anywhere). Incomplete snapshots are never recovery candidates:
    # :meth:`SnapshotStore.latest` skips them, which is exactly the
    # <=1-step-lost-work bound (the last COMPLETE snapshot predates the
    # kill by at most one cadence tick).
    complete: bool = True

    def nbytes(self) -> int:
        return sum(
            a.nbytes
            for store in self.primary.values()
            for shards in store.values()
            for a in shards.values()
        )


@dataclass
class RedundancyPlan:
    """Which hosts can reconstruct which leaves of a snapshot.

    ``kind[path]`` is ``"replicated"`` (every host holds a full copy —
    the DP case) or ``"sharded"`` (hosts hold disjoint shards — the FSDP
    case, protected by the ring mirror)."""

    n_hosts: int
    kind: dict[str, str]

    @classmethod
    def from_snapshot(cls, snap: InMemorySnapshot) -> "RedundancyPlan":
        kind: dict[str, str] = {}
        for leaf in snap.leaves:
            full = tuple((0, d) for d in leaf.shape)
            # Replicated iff every host's primary holds the full-extent
            # shard of this leaf.
            replicated = all(
                full in snap.primary.get(h, {}).get(leaf.path, {})
                for h in range(snap.n_hosts)
                if snap.primary.get(h)
            ) and any(snap.primary.get(h) for h in range(snap.n_hosts))
            kind[leaf.path] = "replicated" if replicated else "sharded"
        return cls(n_hosts=snap.n_hosts, kind=kind)

    def recovery_set(
        self, snap: InMemorySnapshot, alive: set[int]
    ) -> dict[str, list[tuple[int, str, ShardKey]]]:
        """Minimal surviving source set per leaf: a list of
        ``(host, tier, key)`` reads (tier ``"primary"`` or ``"mirror"``)
        that together reconstruct the leaf's full extent. Raises
        :class:`SnapshotIncompleteError` when some shard survives
        nowhere among ``alive`` (primary AND mirror both gone)."""
        out: dict[str, list[tuple[int, str, ShardKey]]] = {}
        needed = {leaf.path: set() for leaf in snap.leaves}
        for path, key in snap.shard_sha:
            needed[path].add(key)
        for leaf in snap.leaves:
            picks: list[tuple[int, str, ShardKey]] = []
            if self.kind.get(leaf.path) == "replicated":
                full = tuple((0, d) for d in leaf.shape)
                src = self._find(snap, leaf.path, full, alive)
                if src is None:
                    raise SnapshotIncompleteError(
                        f"snapshot step {snap.step}: replicated leaf "
                        f"{leaf.path} survives on no alive host {sorted(alive)}"
                    )
                picks.append((src[0], src[1], full))
            else:
                for key in sorted(needed[leaf.path]):
                    src = self._find(snap, leaf.path, key, alive)
                    if src is None:
                        raise SnapshotIncompleteError(
                            f"snapshot step {snap.step}: shard {key} of "
                            f"{leaf.path} survives on no alive host "
                            f"{sorted(alive)} (primary owner and ring "
                            "mirror both lost)"
                        )
                    picks.append((src[0], src[1], key))
            out[leaf.path] = picks
        return out

    @staticmethod
    def _find(
        snap: InMemorySnapshot, path: str, key: ShardKey, alive: set[int]
    ) -> tuple[int, str] | None:
        for h in sorted(alive):
            if key in snap.primary.get(h, {}).get(path, {}):
                return (h, "primary")
        for h in sorted(alive):
            if key in snap.mirror.get(h, {}).get(path, {}):
                return (h, "mirror")
        return None


class SnapshotStore:
    """Double-buffered async snapshotter over a set of virtual hosts.

    ``hosts`` is a :class:`dtc_tpu.resilience.elastic.VirtualHosts` (or
    anything with ``n_hosts`` and ``host_of(device) -> int``).
    ``on_event`` (typically a :class:`RecoveryBus` post) receives one
    ``snapshot`` record per commit — the commit happens on the worker
    thread, so events ride the bus, never a Telemetry handle.
    """

    def __init__(
        self,
        hosts: Any,
        *,
        keep: int = 4,
        on_event: Callable[..., None] | None = None,
    ):
        self.hosts = hosts
        self.on_event = on_event
        self._committed: deque[InMemorySnapshot] = deque(maxlen=max(keep, 1))
        self._queue: queue.Queue = queue.Queue()
        self._pending = 0
        self._lock = threading.Lock()
        self.skipped = 0          # cadence ticks dropped (commit in flight)
        self.commits = 0
        self._closed = False
        self._thread = threading.Thread(
            target=self._worker, name="dtc-snapshot-commit", daemon=True
        )
        self._thread.start()

    # ---- hot-loop side (no host syncs) -----------------------------------
    def begin(self, step: int, state: PyTree, meta: dict | None = None) -> bool:
        """Dispatch an async snapshot of ``state`` tagged ``step``.

        Device-side ``jnp.copy`` per leaf (the copy, not the live state,
        is transferred — so the next step's donation can reuse the live
        buffers while the transfer is still in flight), then
        ``copy_to_host_async``, then hand-off to the commit thread.
        Returns False (and counts a skip) while a previous commit is
        still pending — double-buffering, bounded memory."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            # Double-buffered: one commit landing + one queued behind it.
            # A third cadence tick is SKIPPED (counted), so a slow commit
            # thread bounds in-flight device copies at two snapshots —
            # and the <=1-step-lost-work gate holds as long as a commit
            # takes under two steps, without ever blocking the hot loop.
            if self._pending >= 2:
                self.skipped += 1
                return False
            self._pending += 1
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        paths = ["/".join(_key_names(p)) for p, _ in flat]
        copies = []
        for _, leaf in flat:
            c = jnp.copy(leaf)
            c.copy_to_host_async()
            copies.append(c)
        # Alive set frozen NOW, on the hot loop's thread: a dead host can
        # store nothing, and the commit thread must judge by the roster as
        # of the snapshot's step, not as of commit time.
        alive = set(getattr(self.hosts, "alive", range(self.hosts.n_hosts)))
        self._queue.put((step, paths, copies, treedef, dict(meta or {}), alive))
        return True

    # ---- commit thread ---------------------------------------------------
    def _worker(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                self._queue.task_done()
                return
            try:
                self._commit(*job)
            except Exception as e:  # a failed commit must not kill training
                if self.on_event is not None:
                    self.on_event(
                        "recovery", action="snapshot_commit_failed",
                        step=job[0], reason=f"{type(e).__name__}: {e}",
                    )
            finally:
                with self._lock:
                    self._pending -= 1
                self._queue.task_done()

    def _commit(self, step, paths, copies, treedef, meta, alive) -> None:
        n = self.hosts.n_hosts
        snap = InMemorySnapshot(
            step=step, n_hosts=n, meta=meta, treedef=treedef,
            primary={h: {} for h in range(n)},
        )
        digest = hashlib.sha256()
        for path, arr in zip(paths, copies):
            spec = getattr(arr.sharding, "spec", None)
            snap.leaves.append(
                LeafMeta(path=path, shape=tuple(arr.shape),
                         dtype=arr.dtype, spec=spec)
            )
            for shard in arr.addressable_shards:
                host = self.hosts.host_of(shard.device)
                if host not in alive:
                    # A dead host stores nothing. If the shard exists only
                    # there, this snapshot comes out incomplete below and
                    # is excluded from recovery — the honest emulation of
                    # "no complete checkpoint can form after the host died".
                    continue
                key = shard_key(shard.index, arr.shape)
                store = snap.primary[host].setdefault(path, {})
                if key in store:
                    continue  # replicated leaf: one copy per host suffices
                data = np.asarray(shard.data)
                store[key] = data
                if (path, key) not in snap.shard_sha:
                    snap.shard_sha[(path, key)] = _sha(data)
        # Completeness: the distinct filed shards of every leaf must tile
        # its full extent (shards from one sharding are disjoint, so a
        # volume check is exact).
        covered: dict[str, int] = {}
        for (path, key) in snap.shard_sha:
            vol = 1
            for a, b in key:
                vol *= b - a
            covered[path] = covered.get(path, 0) + (vol if key else 1)
        for leaf in snap.leaves:
            full = 1
            for d in leaf.shape:
                full *= d
            if covered.get(leaf.path, 0) < max(full, 1):
                snap.complete = False
                break
        for (path, key), h in sorted(snap.shard_sha.items()):
            digest.update(path.encode())
            digest.update(repr(key).encode())
            digest.update(h.encode())
        snap.sha256 = digest.hexdigest()
        # Ring mirror: host h's shard-set also lives at the next ALIVE
        # host after h (ring order). Dict of references — the arrays are
        # written once and never mutated; a real transport serializes
        # them over DCN here instead.
        live = sorted(h for h in range(n) if snap.primary.get(h))
        for h in live:
            for off in range(1, n):
                peer = (h + off) % n
                if peer in alive:
                    if peer != h:
                        dst = snap.mirror.setdefault(peer, {})
                        for path, shards in snap.primary[h].items():
                            dst.setdefault(path, {}).update(shards)
                    break
        if snap.complete:
            self._committed.append(snap)
        # An incomplete commit (taken after a host died) is REPORTED but
        # never retained: it can never be a recovery target, and letting
        # it into the bounded keep-ring would evict the complete
        # snapshots recovery actually needs (keep=2 with miss_limit=2
        # would otherwise lose both complete candidates to the two
        # post-kill partials before detection even fires).
        self.commits += 1
        if self.on_event is not None:
            self.on_event(
                "snapshot", step=step, sha256=snap.sha256[:16],
                bytes=snap.nbytes(), skipped=self.skipped, tier="memory",
                complete=snap.complete,
            )

    # ---- consumer side ---------------------------------------------------
    def drain(self) -> None:
        """Block until every queued commit has landed (recovery paths call
        this OUTSIDE the hot loop, before choosing a restore target)."""
        self._queue.join()

    def latest(self, max_step: int | None = None) -> InMemorySnapshot | None:
        """Newest COMPLETE committed snapshot (optionally at or below
        ``max_step`` — the anomaly path restores from BEFORE the first
        poisoned loss). Incomplete commits (taken after a host died) are
        never candidates."""
        for snap in reversed(self._committed):
            if not snap.complete:
                continue
            if max_step is None or snap.step <= max_step:
                return snap
        return None

    def drop_primary(self, host: int) -> bool:
        """Chaos hook (``lose_snapshot_at_step``): ``host``'s snapshot
        RAM is lost — its primary store AND the mirror shards it held
        for its ring-previous host vanish from EVERY retained snapshot
        (dropping only the primary would let a drill "recover" from
        mirror bytes the fault claims were destroyed — the emulation
        must never cheat). A recovery before the next complete commit
        must fall back to the victim's OWN mirror at its ring-next host.
        Commits AFTER the drop are fresh writes and land intact, so the
        fault only bites when configured at (or just before) the failure
        it composes with — the tests pin it to the kill step. Pending
        commits are drained first so the drop covers the snapshot a
        recovery would pick."""
        self.drain()
        dropped = False
        for snap in self._committed:
            if snap.primary.get(host) or snap.mirror.get(host):
                snap.primary[host] = {}
                snap.mirror[host] = {}
                dropped = True
        return dropped

    def restore(
        self, snap: InMemorySnapshot, alive: set[int], mesh: Any
    ) -> tuple[PyTree, bool]:
        """Reconstruct the full state from surviving copies and place it on
        ``mesh`` (the CURRENT mesh — possibly smaller than the one the
        snapshot was taken on) via fresh NamedShardings. Returns
        ``(state, used_mirror)``. Every shard read is re-hashed against
        its commit-time sha256; a mismatch excludes that copy (falling
        back to the peer) and, with no intact copy left, raises
        :class:`SnapshotIncompleteError`."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from dtc_tpu.train.train_step import normalize_spec

        plan = RedundancyPlan.from_snapshot(snap)
        sources = plan.recovery_set(snap, alive)
        used_mirror = False
        leaves_out = []
        for leaf in snap.leaves:
            full = tuple((0, d) for d in leaf.shape)
            out: np.ndarray | None = None
            for host, tier, key in sources[leaf.path]:
                store = (snap.primary if tier == "primary" else snap.mirror)
                data = store[host][leaf.path][key]
                if _sha(data) != snap.shard_sha[(leaf.path, key)]:
                    # Damaged copy: try the other tier / another host.
                    alt = self._intact_copy(snap, leaf.path, key, alive)
                    if alt is None:
                        raise SnapshotIncompleteError(
                            f"snapshot step {snap.step}: every surviving "
                            f"copy of {leaf.path} shard {key} fails its "
                            "integrity hash"
                        )
                    host, tier, data = alt
                if tier == "mirror":
                    used_mirror = True
                if key == full:
                    out = data
                    break
                if out is None:
                    out = np.empty(leaf.shape, dtype=data.dtype)
                out[tuple(slice(a, b) for a, b in key)] = data
            spec = normalize_spec(
                leaf.spec if leaf.spec is not None else P(), mesh
            )
            leaves_out.append(
                jax.device_put(out, NamedSharding(mesh, spec))
            )
        state = jax.tree_util.tree_unflatten(snap.treedef, leaves_out)
        return state, used_mirror

    @staticmethod
    def _intact_copy(snap, path, key, alive):
        for h in sorted(alive):
            for tier, store in (("primary", snap.primary),
                                ("mirror", snap.mirror)):
                data = store.get(h, {}).get(path, {}).get(key)
                if data is not None and _sha(data) == snap.shard_sha[(path, key)]:
                    return h, tier, data
        return None

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._queue.put(None)
        self._thread.join(timeout=5.0)


def _key_names(path: tuple) -> list[str]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return names
