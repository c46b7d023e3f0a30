"""DeviceImageStore — epoch-versioned, double-buffered on-device images.

The device side of the incremental control plane (DESIGN.md §3.5).  A store
wraps one :class:`~repro.core.protocol.ConsistentHash` host state and keeps
its :class:`~repro.core.protocol.DeviceImage` resident on device:

  * **stable shapes** — arrays are allocated 128-padded with headroom
    (``headroom×`` the initial size for the growable algorithms), so churn
    edits never reshape device buffers; ``n`` travels as a dynamic scalar;
  * **delta application** — ``sync()`` drains the host's
    ``device_delta(epoch)`` and applies it as an O(changed-words) scatter
    (functional jnp ``.at[].set`` or the Pallas apply-delta kernel,
    ``kernels/delta_apply.py``) instead of re-transferring an O(n)
    snapshot;
  * **double-buffered epochs** — applying never mutates the serving
    buffers: the epoch-N image stays valid (and keeps answering bulk
    lookups) while epoch N+1 is materialized, then the store flips
    atomically (a python reference swap).  ``image()`` is the current
    front; ``previous_image()`` is the retained epoch the migration-diff
    kernel compares against.

Snapshot rebuilds still happen — but only when they must: when the host's
bounded delta log no longer covers the store's epoch, or when Memento/Jump
growth outruns the padded capacity (rebuilt with doubled headroom, so the
amortized cost stays O(1) per event).  ``last_sync``/``totals`` expose
which path ran and how many 32-bit words crossed host→device — the numbers
the churn benchmark reports.

Epoch advancement comes in two flavours (DESIGN.md §9.1):

  * ``sync()``        — prepare + flip in one call (the classic path);
  * ``sync_async()``  — dispatch the delta-apply scatter and return a
    :class:`SyncHandle` WITHOUT flipping.  The front image keeps serving
    epoch N the whole time the device materializes N+1; ``handle.commit()``
    (or the store's ``poll()``/``flush()``) performs the deferred atomic
    flip, so delta-apply latency hides behind lookup work instead of
    adding to it.  One handle may be in flight at a time; starting another
    sync first commits the pending one, so epochs stay linear.

The store is overlay-agnostic: a bounded-load state (DESIGN.md §4.2)
simply adds a bucket-indexed ``load`` word array to its image, and load
changes ride the same delta path (``_fits`` sizes it to the bucket-id
space).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np

from repro.obs.metrics import default_registry as _default_obs
from .protocol import (ALGORITHM_REGISTRY, ConsistentHash, DeviceImage,
                       ImageDelta, required_lengths, round_up)


def delta_fits(caps: dict[str, int], delta: ImageDelta, *,
               compact: bool = False) -> bool:
    """Do buffers of the given per-array lengths absorb ``delta``?

    The ONE capacity rule shared by the store's delta-vs-snapshot decision
    and the replication publisher's cursor (``launch/replicate.py``):
    ``caps`` maps array name → allocated (or wire-announced) length, and
    the delta fits iff every array a lookup at ``delta.n`` may gather from
    is long enough.  ``compact`` switches Memento to its packed bitmap rule
    (32 buckets per ``state`` word); the bounded-load ``load`` overlay is
    bucket-indexed regardless of layout.  Keeping leader store and
    publisher on the same predicate is what lets the publisher decide
    snapshot-vs-delta for every follower at once (the leader-decides
    invariant, DESIGN.md §9.3).
    """
    if compact and delta.algo == "memento":
        # the bitmap is the bucket-indexed array: 32 buckets per word.
        needed = {"state": -(-delta.n // 32)}
    else:
        needed = dict(required_lengths(delta.algo, delta.n))
    if "load" in caps:  # bounded-load overlay: load words are bucket-indexed
        needed["load"] = delta.n
    return all(caps.get(name, 0) >= need for name, need in needed.items())


@dataclass
class SyncStats:
    """What one ``sync()`` did."""

    mode: str            # "noop" | "delta" | "snapshot"
    events: int          # membership events covered
    words: int           # 32-bit words transferred host→device
    epoch: int           # store epoch after the sync


@dataclass
class SyncTotals:
    syncs: int = 0
    delta_applies: int = 0
    snapshot_rebuilds: int = 0
    events: int = 0
    words: int = 0


class SyncHandle:
    """One in-flight ``sync_async()``: epoch N+1 materializing off the hot path.

    The handle owns the not-yet-front image whose scatter (or snapshot
    transfer) has been *dispatched* but whose epoch flip is deferred.  The
    store keeps serving the old front the whole time; nothing observable
    changes until ``commit()`` (blocking) or ``poll()`` (non-blocking,
    flips only if the device result is ready) lands the flip.  Handles are
    idempotent — ``commit()`` after the flip just returns the stats — and
    the flip itself happens under the store's lock, so concurrent lookup
    threads always observe either the complete old epoch or the complete
    new one, never a torn mix.
    """

    def __init__(self, store: "DeviceImageStore", stats: SyncStats,
                 new_front: DeviceImage | None,
                 new_mirror: dict | None = None):
        self._store = store
        self._stats = stats
        self._new = new_front           # None → noop: nothing to flip
        self._new_mirror = new_mirror
        self._done = new_front is None
        if self._done:
            store._account(stats)

    @property
    def done(self) -> bool:  # obs-exempt: pure accessor
        return self._done

    @property
    def stats(self) -> SyncStats:  # obs-exempt: pure accessor
        """Target-epoch stats (valid before and after the flip)."""
        return self._stats

    def ready(self) -> bool:
        """True iff every dispatched device buffer has materialized.


        Non-blocking: uses ``jax.Array.is_ready()``.  Arrays without the
        probe (plain numpy in interpret paths) count as ready.
        """
        # obs-exempt: readiness probe only, no device dispatch
        if self._done:
            return True
        return all(v.is_ready() for v in self._new.arrays.values()
                   if hasattr(v, "is_ready"))

    def poll(self) -> bool:
        """Flip iff the device result is ready; never blocks.  Returns
        whether the handle is done (flipped or was a noop)."""
        # obs-exempt: delegates to commit(), which records the flip
        if not self._done and self.ready():
            self.commit()
        return self._done

    def commit(self) -> SyncStats:
        """Block until epoch N+1 is materialized, then flip atomically."""
        with self._store._lock:
            if self._done:
                return self._stats
            reg = self._store._obs()
            with reg.span("store.sync.commit", epoch=self._stats.epoch):
                with reg.span("store.sync.materialize"):
                    for v in self._new.arrays.values():
                        if hasattr(v, "block_until_ready"):
                            v.block_until_ready()
                with reg.span("store.sync.flip", epoch=self._stats.epoch):
                    self._store._flip(self._new, self._new_mirror,
                                      self._stats)
            self._done = True
            if self._store._pending is self:
                self._store._pending = None
            reg.gauge("store.pending").set(0)
        return self._stats


class DeviceImageStore:
    """Double-buffered device image of a ConsistentHash, updated by deltas."""

    def __init__(self, ch: ConsistentHash, *, plane: str = "jnp",
                 headroom: int = 2, interpret: bool | None = None,
                 compact: bool = False, registry=None):
        if plane not in ("jnp", "pallas"):
            raise ValueError(f"unknown plane {plane!r}")
        self._ch = ch
        self._registry = registry  # None → follow the process default
        self.plane = plane
        self.headroom = max(1, headroom)
        self.compact = compact
        self._mirror: dict | None = None  # host copy of the packed arrays
        if interpret is None:
            from repro.kernels.engine import default_interpret
            interpret = default_interpret()
        self._interpret = interpret
        self.totals = SyncTotals()
        self.last_sync: SyncStats | None = None
        self._prev: DeviceImage | None = None
        self._lock = threading.RLock()
        self._pending: SyncHandle | None = None
        self._rebuild()

    def _obs(self):
        """The live telemetry registry (DESIGN.md §11): the injected one,
        else whatever the process default currently is — so ``enable()``
        after construction still reaches existing stores."""
        return self._registry or _default_obs()

    # -- buffers ---------------------------------------------------------------
    def _snapshot(self) -> tuple[DeviceImage, dict | None]:
        """Build (dispatch, don't install) a full snapshot image + mirror."""
        import jax.numpy as jnp

        algo = getattr(self._ch, "image_algo", self._ch.name)
        if not ALGORITHM_REGISTRY[algo].fixed_capacity:  # growth: headroom
            cap = round_up(max(self.headroom * self._image_size_hint(), 128))
        else:  # fixed overall capacity a: padding beyond a is never read
            cap = None
        img = self._ch.device_image(capacity=cap)
        mirror = None
        if self.compact:
            from .packing import pack_image

            # slot headroom 2 → ≤ 0.25 load factor at rebuild, so epoch
            # deltas insert in place; the numpy mirror is the host copy
            # packed_delta_updates edits to derive device scatters.
            img = pack_image(img, slot_headroom=2)
            mirror = {k: np.array(v) for k, v in img.arrays.items()}
        front = DeviceImage(
            algo=img.algo, n=img.n,
            arrays={k: jnp.asarray(v) for k, v in img.arrays.items()},
            scalars=dict(img.scalars), epoch=img.epoch,
            packed=img.packed)
        return front, mirror

    def _rebuild(self) -> None:
        """Full snapshot upload (init, log overflow, or capacity growth)."""
        self._front, self._mirror = self._snapshot()

    def _image_size_hint(self) -> int:
        return self._ch.size

    @property
    def epoch(self) -> int:  # obs-exempt: pure accessor
        return self._front.epoch

    @property
    def capacity(self) -> dict[str, int]:  # obs-exempt: pure accessor
        return {k: int(v.shape[0]) for k, v in self._front.arrays.items()}

    def image(self) -> DeviceImage:  # obs-exempt: pure accessor
        """The serving (front) image.  Immutable: syncs replace, never edit."""
        return self._front

    def previous_image(self) -> DeviceImage | None:  # obs-exempt: pure accessor
        """The retained pre-sync epoch (migration-diff comparand), if any."""
        return self._prev

    # -- epoch advancement -----------------------------------------------------
    def sync(self) -> SyncStats:
        """Advance the device image to the host's current epoch.

        Applies an O(changed-words) delta when the host log covers our
        epoch and capacity suffices; falls back to a full snapshot rebuild
        otherwise.  Either way the old front buffer is retained as
        ``previous_image()`` and the flip is atomic.  Any pending async
        epoch is committed first, so epochs stay linear.
        """
        reg = self._obs()
        t0 = time.perf_counter_ns() if reg.active else 0
        with reg.span("store.sync", mode="block"):
            self.flush()
            with reg.span("store.sync.dispatch"):
                new, mirror, stats = self._prepare()
            with self._lock:
                if new is not None:
                    with reg.span("store.sync.flip", epoch=stats.epoch):
                        self._flip(new, mirror, stats)
                else:
                    self._account(stats)
        if reg.active:
            reg.histogram("store.sync.us", mode=stats.mode).observe(
                (time.perf_counter_ns() - t0) / 1e3)
        return stats

    def sync_async(self) -> SyncHandle:
        """Dispatch epoch N+1 (delta scatter or snapshot transfer) without
        flipping and without blocking on the device result.

        The front image keeps serving epoch N until the returned
        :class:`SyncHandle` is committed — by ``handle.commit()``, the
        store's ``poll()``/``flush()``, or implicitly by the next
        ``sync``/``sync_async`` call (one handle in flight at a time, so
        epochs remain linear).  Lookups issued meanwhile are epoch-N
        consistent; lookups after the commit are epoch-N+1 consistent.
        """
        reg = self._obs()
        with reg.span("store.sync.dispatch", mode="overlap"):
            self.flush()
            new, mirror, stats = self._prepare()
        handle = SyncHandle(self, stats, new, mirror)
        if not handle.done:
            self._pending = handle
            reg.gauge("store.pending").set(1)
        return handle

    def poll(self) -> bool:
        """Commit the pending async epoch iff its device result is ready
        (never blocks).  True when no flip remains outstanding."""
        # obs-exempt: delegates to SyncHandle.commit (instrumented)
        h = self._pending
        return h.poll() if h is not None else True

    def flush(self) -> SyncStats | None:
        """Commit the pending async epoch, blocking if needed."""
        # obs-exempt: delegates to SyncHandle.commit (instrumented)
        h = self._pending
        return h.commit() if h is not None else None

    @property
    def pending(self) -> SyncHandle | None:  # obs-exempt: pure accessor
        """The in-flight ``sync_async`` handle, if any."""
        return self._pending

    def _prepare(self) -> tuple[DeviceImage | None, dict | None, SyncStats]:
        """Drain the host delta and dispatch (but do not install) the
        next-epoch image.  Returns ``(new_front, new_mirror, stats)``;
        ``new_front is None`` means nothing to flip (noop)."""
        delta = self._drain_delta()
        applied = None
        if delta is not None and delta.events == 0:
            return None, None, SyncStats("noop", 0, 0, self.epoch)
        if delta is not None and self._fits(delta) and (
                applied := (self._apply_packed(delta) if self.compact
                            else (self._apply(delta), delta.num_words()))
        ) is not None:
            new, words = applied
            return new, self._mirror, SyncStats("delta", delta.events, words,
                                                new.epoch)
        events = getattr(self._ch, "epoch", self._front.epoch) - self._front.epoch
        new, mirror = self._snapshot()
        words = sum(int(v.size) for v in new.arrays.values()) + 1
        return new, mirror, SyncStats("snapshot", events, words, new.epoch)

    def _flip(self, new: DeviceImage, mirror: dict | None,
              stats: SyncStats) -> None:
        """Atomically install epoch N+1 (caller holds ``_lock``)."""
        old = self._front
        self._front = new
        self._mirror = mirror
        self._prev = old
        self._account(stats)

    def _account(self, stats: SyncStats) -> None:
        if stats.mode == "delta":
            self.totals.delta_applies += 1
        elif stats.mode == "snapshot":
            self.totals.snapshot_rebuilds += 1
        self.totals.syncs += 1
        self.totals.events += stats.events
        self.totals.words += stats.words
        self.last_sync = stats
        reg = self._obs()
        if reg.active:  # mirror SyncTotals onto the registry (one source
            reg.counter("store.syncs").inc()  # of counters for exporters)
            reg.counter("store.sync_events").inc(stats.events)
            if stats.mode == "delta":
                reg.counter("store.delta_applies").inc()
                reg.counter("store.delta_words").inc(stats.words)
            elif stats.mode == "snapshot":
                reg.counter("store.snapshot_rebuilds").inc()
                reg.counter("store.snapshot_words").inc(stats.words)
            reg.sink.emit("sync", mode=stats.mode, events=stats.events,
                          words=stats.words, epoch=stats.epoch)

    def _drain_delta(self) -> ImageDelta | None:
        ch = self._ch
        if not hasattr(ch, "device_delta"):
            return None  # non-emitting implementation: snapshots only
        return ch.device_delta(self._front.epoch)

    def _fits(self, delta: ImageDelta) -> bool:
        return delta_fits(self.capacity, delta, compact=self.compact)

    def _apply(self, delta: ImageDelta) -> DeviceImage:
        from repro.kernels.delta_apply import apply_updates

        arrays = apply_updates(self._front.arrays, delta.updates,
                               plane=self.plane, interpret=self._interpret)
        return DeviceImage(algo=delta.algo, n=delta.n, arrays=arrays,
                           scalars=dict(delta.scalars), epoch=delta.epoch)

    def _apply_packed(self, delta: ImageDelta) -> tuple[DeviceImage, int] | None:
        """Translate a dense-layout delta into packed-layout scatters and
        apply them, or return ``None`` (→ snapshot rebuild) when the packed
        buffers cannot absorb it (bitmap outgrown, slots saturated, or a
        value overflows a narrowed dtype)."""
        from .packing import packed_delta_updates
        from repro.kernels.delta_apply import scatter_update

        updates = packed_delta_updates(self._mirror, delta)
        if updates is None:
            return None
        arrays = dict(self._front.arrays)
        words = 0
        for name, (idx, vals) in updates.items():
            if not len(idx):
                continue
            arrays[name] = scatter_update(arrays[name], idx, vals,
                                          plane=self.plane,
                                          interpret=self._interpret)
            words += 2 * len(idx)
        img = DeviceImage(algo=delta.algo, n=delta.n, arrays=arrays,
                          scalars=dict(delta.scalars), epoch=delta.epoch,
                          packed=True)
        return img, words

    # -- data plane ------------------------------------------------------------
    def lookup(self, keys, *, plane: str | None = None, k: int = 1,
               **kw) -> np.ndarray:
        """Bulk lookup against the front image via the unified engine
        (DESIGN.md §6; jitted jnp or one Pallas launch).

        Compiles once per engine configuration and shape set; the store's
        stable padded capacities make every subsequent epoch a cache hit.
        ``k > 1`` returns [K, k] replica sets in the same single program.
        Defaults to the store's configured apply plane.  Spans (each with
        its ``.us`` histogram): ``store.lookup`` around the engine's
        ``engine.dispatch`` and ``store.fetch``, the wait for the answer
        and its copy to the host.
        """
        from repro.kernels.engine import engine_lookup

        plane = plane or self.plane
        reg = self._obs()
        with reg.timed("store.lookup"):
            dev = engine_lookup(keys, self._front, k=k, plane=plane,
                                registry=reg, **kw)
            with reg.timed("store.fetch"):
                out = np.asarray(dev)
                reg.flush_device()
        if reg.active:
            reg.counter("store.lookups").inc()
            reg.counter("store.lookup_keys").inc(int(out.shape[0]))
        return out

    def migration_diff(self, keys, *, plane: str = "jnp", k: int = 1, **kw):
        """Moved-key mask between the retained epoch and the front epoch
        (one fused engine launch; ``k > 1`` diffs whole replica sets)."""
        from repro.kernels.engine import engine_diff

        if self._prev is None:
            raise ValueError("no previous epoch retained (sync() first)")
        with self._obs().span("store.diff", epoch=self._front.epoch):
            return engine_diff(keys, self._prev, self._front, plane=plane,
                               k=k, **kw)
