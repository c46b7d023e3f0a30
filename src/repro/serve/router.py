"""Session → replica router: consistent hashing with KV-cache affinity.

The serving-side face of the paper: requests carry a session id (prefix /
KV-cache identity); the router consistent-hashes sessions onto model
replicas so

  * a session always lands on the replica holding its KV cache (affinity),
  * replica failure remaps ONLY that replica's sessions (minimal disruption)
    — the rest keep their warm caches,
  * replicas added back (restored) steal only the sessions that belonged to
    them (monotonicity), and (with Memento/Jump) the fleet can grow without
    bound.

The router is algorithm-pluggable: any :class:`~repro.core.ConsistentHash`
(Memento — the default —, Anchor, Dx, Jump) drives placement through the
same protocol.  Bulk routing (e.g. batch admission of thousands of queued
requests) runs on the device data plane through a
:class:`~repro.core.DeviceImageStore`: ``fail_replica``/``restore_replica``
push O(changed-words) epoch deltas to the device instead of nulling and
rebuilding the O(n) image (DESIGN.md §3.5), and lookups keep serving the
old epoch until the flip.  Batch lookups are single launches of the
unified engine (DESIGN.md §6); :meth:`SessionRouter.route_stream` fans
streams of batches across every device via the mesh-sharded
:class:`~repro.serve.plane.ShardedLookupPlane`.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from repro.core import ConsistentHash, DeviceImageStore, make_hash
from repro.core.hashing import key_to_u32, np_key_to_u32
from repro.obs.metrics import count_compiles, ensure_real
from repro.obs.metrics import default_registry as _default_obs


class RouterStats:
    """Live view over the router's ``router.*`` telemetry counters.

    The historical dataclass API is preserved — ``stats.routed`` reads,
    ``stats.routed += n`` writes — but the counters on a
    :class:`~repro.obs.metrics.MetricRegistry` are the store, so the same
    numbers flow to the exposition/snapshot exporters (DESIGN.md §11).
    With telemetry off the view rides a private registry
    (:func:`~repro.obs.metrics.ensure_real`), so the API never goes dark.
    Attribute writes are deltas on monotonic counters; rewinding (setting
    a smaller value) is a no-op.
    """

    FIELDS = ("routed", "moved_on_failure", "affinity_hits", "failovers")

    def __init__(self, registry=None):
        object.__setattr__(self, "_counters",
                           {f: ensure_real(registry).counter(f"router.{f}")
                            for f in self.FIELDS})

    def __getattr__(self, name):
        counters = object.__getattribute__(self, "_counters")
        if name in counters:
            return counters[name].value
        raise AttributeError(name)

    def __setattr__(self, name, value) -> None:
        counters = self._counters
        if name in counters:
            delta = int(value) - counters[name].value
            if delta > 0:
                counters[name].inc(delta)
            return
        object.__setattr__(self, name, value)

    def as_dict(self) -> dict:
        return {f: getattr(self, f) for f in self.FIELDS}

    def __repr__(self) -> str:
        inner = ", ".join(f"{f}={getattr(self, f)}" for f in self.FIELDS)
        return f"RouterStats({inner})"


class SessionRouter:
    """Session → replica router; with ``replicas_k > 1`` it is replica-aware
    (DESIGN.md §4.3): every session has a k-replica set (salted ``lookup_k``,
    so replica 0 is the classic placement) and a *marked-failed* replica
    fails over to replica r+1 **before** any membership delta lands — the
    instant a health checker calls :meth:`mark_failed`, routing avoids the
    node, while the epoch delta (``fail_replica``) catches up asynchronously.
    """

    def __init__(self, num_replicas: int, *, algo: str | ConsistentHash = "memento",
                 capacity: int | None = None, use_device_plane: bool = False,
                 max_sessions: int = 1_000_000, replicas_k: int = 1,
                 store: DeviceImageStore | None = None,
                 compact_images: bool = False,
                 block_rows: int | None = None,
                 sync_mode: str = "block", registry=None):
        if isinstance(algo, str):
            # variant="32": host lookups bit-identical to the device plane.
            self.ch = make_hash(algo, num_replicas, capacity=capacity, variant="32")
        else:
            self.ch = algo
        if replicas_k < 1:
            raise ValueError("replicas_k must be ≥ 1")
        if sync_mode not in ("block", "overlap"):
            raise ValueError(f"unknown sync_mode {sync_mode!r}")
        self.replicas_k = replicas_k
        # "overlap": membership deltas ride sync_async() — the flip lands at
        # the next batch boundary (bounded staleness) instead of stalling
        # the event path for the full delta-apply latency (DESIGN.md §9.2).
        self.sync_mode = sync_mode
        self.use_device_plane = use_device_plane
        # device-plane tuning knobs: compact (packed) device images and an
        # explicit Pallas tile height (None → the autotuner's winner)
        self.compact_images = compact_images
        self.block_rows = block_rows
        self._registry = registry  # None → follow the process default
        if registry is not None:  # device.compile.us: compiles from now on
            count_compiles(registry)
        # stats land on the injected registry when it records, else on the
        # process default, else on the view's own private registry — the
        # public counter API works with telemetry globally off.
        self.stats = RouterStats(registry or _default_obs())
        self.max_sessions = max_sessions
        # session id → last replica (metrics), LRU-bounded: million-session
        # fleets must not grow host memory without limit.
        self._last: OrderedDict = OrderedDict()
        # an injected store (e.g. the scenario driver's) must wrap the SAME
        # host state, or deltas and lookups would split across two clusters
        if store is not None and store._ch is not self.ch:
            raise ValueError("injected store wraps a different host state")
        self._store: DeviceImageStore | None = store
        self._plane = None    # lazy ShardedLookupPlane (route_stream)
        self._plane_k = None  # lazy k-replica plane (failover streaming)
        # replicas marked failed but whose removal delta has not landed yet:
        # route()/route_batch() fail over around them immediately.
        self._failed: set[int] = set()
        # overlap mode: replica → host epoch whose device landing clears the
        # mark.  While the async removal is in flight, device lookups still
        # serve the pre-removal epoch, so the failover mask must outlive
        # fail_replica() until the flip actually happens.
        self._unmark_at: dict[int, int] = {}

    @property
    def memento(self) -> ConsistentHash:
        """Back-compat alias from the Memento-only router."""  # obs-exempt
        return self.ch

    def _obs(self):
        """The live telemetry registry (injected, else process default)."""
        return self._registry or _default_obs()

    # -- single-request path --------------------------------------------------
    def replica_set(self, session_id) -> list[int]:
        """The session's k distinct candidate replicas (replica 0 = the
        classic single-lookup placement).  k is clamped to the surviving
        fleet so deep failure cascades degrade instead of raising."""
        self._obs().counter("router.replica_set_calls").inc()
        k = min(self.replicas_k, self.ch.working)
        return self.ch.lookup_k(key_to_u32(session_id), k)

    def route(self, session_id) -> int:
        reg = self._obs()
        t0 = time.perf_counter_ns() if reg.active else 0
        self._poll_store()
        if self.replicas_k > 1 and self._failed:
            reps = self.replica_set(session_id)
            # fail over to replica r+1 while the primary is marked failed;
            # if every replica is marked, keep the primary (nothing better).
            r = next((c for c in reps if c not in self._failed), reps[0])
            if r != reps[0]:
                self.stats.failovers += 1
        else:
            r = self.ch.lookup(key_to_u32(session_id))
        self.stats.routed += 1
        if self._last.get(session_id) == r:
            self.stats.affinity_hits += 1
        self._last[session_id] = r
        self._last.move_to_end(session_id)  # no-op for fresh keys
        if len(self._last) > self.max_sessions:
            self._last.popitem(last=False)  # evict the coldest session
        if reg.active:
            reg.histogram("router.route.us").observe(
                (time.perf_counter_ns() - t0) / 1e3)
        return r

    # -- bulk path (device plane) ----------------------------------------------
    def image_store(self) -> DeviceImageStore:
        if self._store is None:
            plane = "pallas" if self.use_device_plane else "jnp"
            self._store = DeviceImageStore(self.ch, plane=plane,
                                           compact=self.compact_images,
                                           registry=self._registry)
        return self._store

    def device_image(self):  # obs-exempt: pure accessor
        return self.image_store().image()

    def _failover_pick(self, sets: np.ndarray) -> np.ndarray:
        """THE failover rule, shared by every batch path: per row of k
        candidate replicas, pick the first not marked failed (all marked →
        keep the primary).  Accepts 1-D input (k clamped to 1 by a
        collapsed fleet)."""
        sets = np.asarray(sets)
        if sets.ndim == 1:
            sets = sets.reshape(-1, 1)
        ok = ~np.isin(sets, sorted(self._failed))
        ok[:, 0] |= ~ok.any(axis=1)  # all failed → keep the primary
        col = ok.argmax(axis=1)
        self.stats.failovers += int((col > 0).sum())
        return sets[np.arange(len(sets)), col]

    def route_batch(self, session_ids: np.ndarray) -> np.ndarray:
        """Session ids → np int32 replicas, in one engine launch.  Spans
        (each with its ``.us`` histogram, all with one ``batch`` id):
        ``router.route_batch`` around ``router.hash`` (the ids' 32-bit
        keys) and the store's ``store.lookup``."""
        reg = self._obs()
        with reg.timed("router.route_batch", batch=reg.next_batch()):
            self._poll_store()
            with reg.timed("router.hash"):
                keys = np_key_to_u32(np.asarray(session_ids))
            plane = "pallas" if self.use_device_plane else "jnp"
            if self.replicas_k > 1 and self._failed:
                # k-replica sets in one device pass; same rule as route()
                return self._failover_pick(self.replica_set_batch(session_ids))
            return self.image_store().lookup(keys, plane=plane,
                                             block_rows=self.block_rows)

    def replica_set_batch(self, session_ids: np.ndarray) -> np.ndarray:
        """k-replica sets for a session batch in one engine launch:
        int32 [len(ids), k], column 0 = the classic placement."""
        reg = self._obs()
        t0 = time.perf_counter_ns() if reg.active else 0
        keys = np_key_to_u32(np.asarray(session_ids))
        plane = "pallas" if self.use_device_plane else "jnp"
        k = min(self.replicas_k, self.ch.working)
        out = self.image_store().lookup(keys, plane=plane, k=k,
                                        block_rows=self.block_rows)
        if reg.active:
            reg.histogram("router.replica_set.us", k=k).observe(
                (time.perf_counter_ns() - t0) / 1e3)
        return out.reshape(-1, 1) if k == 1 else out

    # -- streaming path (mesh-sharded plane) ----------------------------------
    def sharded_plane(self, *, mesh=None, axes=None):
        """The router's :class:`~repro.serve.plane.ShardedLookupPlane` over
        its image store: million-session batches fan out across every
        device, with membership deltas reaching each device through the
        store's epoch sync (DESIGN.md §6)."""
        from repro.serve.plane import ShardedLookupPlane
        if self._plane is None or mesh is not None or axes is not None:
            plane = ShardedLookupPlane(self.image_store(), mesh=mesh,
                                       axes=axes, block_rows=self.block_rows,
                                       sync_mode=self.sync_mode,
                                       registry=self._registry)
            if mesh is None and axes is None:
                self._plane = plane
            return plane
        return self._plane

    def route_stream(self, session_id_batches, *, mesh=None):
        """Stream batches of session ids → np int32 replica batches through
        the mesh-sharded plane.  Membership events applied between batches
        (``fail_replica``/``restore_replica``) are picked up at the next
        batch boundary, and — like :meth:`route_batch` — replicas marked
        failed (:meth:`mark_failed`) are failed over BEFORE their removal
        delta lands.  A replica-unaware router (``replicas_k == 1``)
        streams through the plane's pipelined double-buffered path; a
        replica-aware one dispatches per batch so the failover mask is
        applied with the same rule as the scalar path."""
        reg = self._obs()
        plane = self.sharded_plane(mesh=mesh)
        if self.replicas_k == 1:
            def to_keys():
                for ids in session_id_batches:
                    self.stats.routed += len(ids)
                    reg.counter("router.stream_batches").inc()
                    with reg.timed("router.hash"):
                        keys = np_key_to_u32(np.asarray(ids))
                    yield keys

            yield from plane.route_stream(to_keys())
            return
        kplane = self._replica_plane(mesh)  # built once per stream, not per batch
        for ids in session_id_batches:
            ids = np.asarray(ids)
            self._poll_store()  # overlap: land a ready flip, retire marks
            self.stats.routed += len(ids)
            reg.counter("router.stream_batches").inc()
            keys = np_key_to_u32(ids)
            if not self._failed:
                yield plane.lookup(keys)
            else:
                yield self._failover_pick(kplane.lookup(keys))

    def _replica_plane(self, mesh=None):
        """Sharded k-replica plane for the failover stream path."""
        from repro.serve.plane import ShardedLookupPlane
        k = min(self.replicas_k, self.ch.working)
        if self._plane_k is None or self._plane_k.k != k or mesh is not None:
            plane = ShardedLookupPlane(self.image_store(), mesh=mesh, k=k,
                                       block_rows=self.block_rows,
                                       sync_mode=self.sync_mode,
                                       registry=self._registry)
            if mesh is None:
                self._plane_k = plane
            return plane
        return self._plane_k

    # -- membership ----------------------------------------------------------
    def _push_delta(self) -> None:
        """Mirror the membership event to the device as an epoch delta.

        ``sync_mode='block'`` flips synchronously; ``'overlap'`` dispatches
        the delta apply and defers the flip to the next poll point (a batch
        boundary, or the next membership event)."""
        if self._store is not None:
            if self.sync_mode == "overlap":
                self._store.sync_async()
            else:
                self._store.sync()

    def _poll_store(self) -> None:
        """Overlap-mode poll point: land a ready async epoch (never blocks)
        and retire failover marks whose removal epoch has reached the
        device."""
        if self.sync_mode == "overlap" and self._store is not None:
            self._store.poll()
        if self._unmark_at and self._store is not None:
            ep = self._store.epoch
            for r, until in list(self._unmark_at.items()):
                if ep >= until:
                    del self._unmark_at[r]
                    self._failed.discard(r)

    def mark_failed(self, replica: int) -> None:
        """Health-checker hook: route around ``replica`` NOW, before any
        membership delta is emitted or applied (DESIGN.md §4.3)."""
        self._failed.add(replica)
        self._obs().counter("router.failover_marks").inc()

    def fail_replica(self, replica: int) -> dict:
        reg = self._obs()
        before = dict(self._last)
        self.mark_failed(replica)  # failover active while the delta lands
        removed = False
        try:
            with reg.span("router.fail_replica", replica=replica):
                self.ch.remove(replica)
                removed = True
                self._push_delta()
            reg.counter("router.membership_events", op="fail").inc()
        finally:
            host_ep = getattr(self.ch, "epoch", None)
            if (removed and self.sync_mode == "overlap"
                    and self._store is not None and host_ep is not None
                    and self._store.epoch < host_ep):
                # async removal still in flight: the device plane serves
                # the pre-removal epoch, so keep failing over until the
                # flip lands (_poll_store retires the mark by epoch).
                self._unmark_at[replica] = host_ep
            else:
                # membership reflects the failure (or the removal was
                # invalid): either way the mark must not outlive this call
                self._failed.discard(replica)
        moved = {s for s, r in before.items() if r == replica}
        self.stats.moved_on_failure += len(moved)
        info = {"replica": replica, "sessions_moved": len(moved)}
        if self._store is not None:
            # overlap: the delta is dispatched but not flipped — report the
            # in-flight handle's target-epoch stats, not the stale last_sync
            pend = self._store.pending
            st = pend.stats if pend is not None else self._store.last_sync
            if st is not None:
                info["control_plane"] = {"mode": st.mode, "words": st.words,
                                         "epoch": st.epoch}
        return info

    def restore_replica(self) -> int:
        reg = self._obs()
        with reg.span("router.restore_replica"):
            b = self.ch.add()
            self._push_delta()
        reg.counter("router.membership_events", op="restore").inc()
        return b

    @property
    def replicas(self) -> set[int]:  # obs-exempt: pure accessor
        return self.ch.working_set()


@dataclass
class Request:
    session_id: int
    tokens: list[int] = field(default_factory=list)


class BatchScheduler:
    """Groups admitted requests per replica into decode batches.

    ``assign`` honours ``max_batch`` per replica and returns the overflow
    explicitly — requests beyond a replica's budget are NOT silently
    dropped; they come back in arrival order for the caller to re-queue
    (or are carried in ``self.pending`` and drained first on the next
    ``assign``).
    """

    def __init__(self, router: SessionRouter, max_batch: int):
        self.router = router
        self.max_batch = max_batch
        self.pending: list[Request] = []

    def assign(self, requests: list[Request]) -> tuple[dict[int, list[Request]], list[Request]]:
        """Route ``pending + requests``; returns ``(batches, overflow)``.

        ``batches`` maps replica → at most ``max_batch`` requests.
        ``overflow`` lists the requests that exceeded some replica's
        budget; the scheduler retains them in ``self.pending`` and drains
        them first on the next call, so callers must NOT resubmit them —
        the returned list is for back-pressure telemetry.
        """
        work = self.pending + list(requests)
        ids = np.asarray([r.session_id for r in work], dtype=np.uint64)
        replicas = (self.router.route_batch(ids) if len(ids) else
                    np.zeros((0,), np.int32))
        out: dict[int, list[Request]] = {}
        overflow: list[Request] = []
        for req, rep in zip(work, replicas):
            lst = out.setdefault(int(rep), [])
            if len(lst) < self.max_batch:
                lst.append(req)
            else:
                overflow.append(req)  # back-pressure, not truncation
        self.pending = overflow
        return out, list(overflow)  # copy: callers must not mutate the queue
