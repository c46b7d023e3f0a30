"""ConsistentHash — the one protocol every algorithm in this repo speaks.

Host control plane (paper-faithful, Θ(state) python):
    ``lookup / remove / add / working / size / working_set / memory_bytes``

Device data plane (DESIGN.md §3.3): ``device_image()`` flattens the host
state into a :class:`DeviceImage` — a bundle of flat, 128-padded
int32/uint32 arrays plus the dynamic scalars the lane-synchronous lookups
need.  One image format serves three consumers:

  * ``core/jax_lookup.lookup_image``   — pure-jnp oracle (any backend),
  * ``kernels/engine.engine_lookup``   — the lookup engine's jnp and
    Pallas planes (Mosaic on TPU, interpret mode elsewhere),
  * tests/benchmarks                   — cross-plane equivalence sweeps.

Device control plane (DESIGN.md §3.5): membership churn is epoch-versioned.
Every ``remove()``/``add()`` bumps the algorithm's ``epoch`` and appends an
O(changed-words) record to a bounded delta log; ``device_delta(since)``
composes the records after ``since`` into one :class:`ImageDelta` —
scatter indices/values per named array plus the new dynamic scalars.  A
:class:`~repro.core.image_store.DeviceImageStore` applies deltas to
double-buffered on-device arrays and flips epochs atomically, so bulk
lookups keep serving epoch N while N+1 is applied; images built at a given
epoch stay immutable snapshots of that epoch.  Device lookups are
bit-identical to the host ``lookup`` of the TPU-native ``variant="32"``
state; the default ``variant="64"`` remains paper-faithful host-only.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Protocol, runtime_checkable

import numpy as np


def round_up(x: int, m: int = 128) -> int:
    """Round ``x`` up to a multiple of ``m`` (TPU lane alignment)."""
    return ((x + m - 1) // m) * m


#: Salted re-lookup bound for ``lookup_k`` (DESIGN.md §4.1).  With k ≤ w the
#: probability a salted lookup collides with an already-chosen bucket is
#: ≤ (k−1)/w per try, so exhausting the bound has probability ≤ ((k−1)/w)^CAP
#: — unreachable in practice; the bound exists so the device loops terminate
#: even on adversarial states.  Host and device share the constant so the
#: planes stay bit-identical.
REPLICA_SALT_CAP = 4096


class ReplicatedLookup:
    """Mixin: protocol-generic k-replication by salted re-lookup (DESIGN.md §4.1).

    ``lookup_k(key, k)`` returns k *distinct* working buckets.  Replica 0 is
    the plain ``lookup(key)`` (so k = 1 degenerates to the base algorithm);
    replica j is found by looking up the salted key ``hash2(key, salt)`` for
    salt = 1, 2, … and keeping the first candidate not already chosen.  The
    salt counter is shared across slots, so the construction is a single
    deterministic walk — the same walk the jnp and Pallas planes run
    lane-synchronously (``kernels/engine.py``), bit-identical on
    ``variant="32"`` states.

    Disruption bound: removing bucket b changes a key's replica set only if
    some salted lookup in its walk mapped to b; each salted lookup inherits
    the base algorithm's minimal disruption, so expected slot churn per
    removal is ≤ (k + expected dedup retries)/w — the per-slot analogue of
    the paper's minimal-disruption property (DESIGN.md §4.1).
    """

    def _salt_hash2(self, key: int, salt: int) -> int:
        """The salted re-key — variant-matched so device planes agree."""
        from .hashing import hash2_32, hash2_64

        if getattr(self, "variant", "64") == "32":
            return hash2_32(key, salt)
        return hash2_64(key, salt)

    def lookup_k_filtered(self, key: int, k: int, reject,
                          trace: list | None = None,
                          check_first: bool = False) -> list[int]:
        """The one salted walk every k-replica variant shares.

        ``reject(cand, chosen)`` skips a candidate the way the dedup rule
        skips duplicates (plain ``lookup_k`` passes exactly that rule;
        failure-domain placement adds a domain check — see
        ``runtime/elastic.domain_distinct_replicas``).  Slot 0 is the plain
        lookup, accepted unconditionally unless ``check_first`` — the
        bounded-replica op (``kernels/engine.bounded_replica_sets``) applies
        its load-cap rule to slot 0 too, so even the primary replica walks
        past full buckets.  ``trace``, if given, collects every
        salted-lookup result in walk order (rejected ones included).
        Keeping the walk in ONE place is what keeps the host bit-identical
        to the device planes (``kernels/engine.replica_body``).
        """
        if k < 1:
            raise ValueError("k must be ≥ 1")
        first = self.lookup(key)
        if trace is not None:
            trace.append(first)
        out = [] if check_first and reject(first, []) else [first]
        salt = 1
        while len(out) < k:
            if salt > REPLICA_SALT_CAP:
                raise RuntimeError("replica salt budget exhausted")
            cand = self.lookup(self._salt_hash2(key, salt))
            if trace is not None:
                trace.append(cand)
            if not reject(cand, out):
                out.append(cand)
            salt += 1
        return out

    @staticmethod
    def _reject_duplicate(cand: int, chosen: list[int]) -> bool:
        return cand in chosen

    def lookup_k(self, key: int, k: int) -> list[int]:
        """k distinct working buckets for ``key``; ``lookup_k(key, 1)[0] ==
        lookup(key)``.  Requires ``k ≤ working``."""
        if k > self.working:
            raise ValueError(f"k={k} exceeds working buckets ({self.working})")
        return self.lookup_k_filtered(key, k, self._reject_duplicate)

    def lookup_k_trace(self, key: int, k: int) -> tuple[list[int], list[int]]:
        """``lookup_k`` returning ``(replicas, candidates)`` where
        ``candidates`` lists every salted-lookup result in walk order
        (including dedup-rejected ones) — the instrument the replica-stability
        property tests use: a removal can change the set only if the removed
        bucket appears among the candidates."""
        if k > self.working:
            raise ValueError(f"k={k} exceeds working buckets ({self.working})")
        cands: list[int] = []
        out = self.lookup_k_filtered(key, k, self._reject_duplicate,
                                     trace=cands)
        return out, cands


def replica_sets(h, keys, k: int) -> np.ndarray:
    """Numpy oracle: ``lookup_k`` over a key batch → int32 [len(keys), k].

    The ground truth the device planes (`kernels/engine.py`) are
    tested against; per-key scalar walk on the host control plane.
    """
    keys = np.asarray(keys)
    out = np.empty((len(keys), k), dtype=np.int32)
    for i, key in enumerate(keys):
        out[i] = h.lookup_k(int(key), k)
    return out


@dataclass
class DeviceImage:
    """Flat device image of a consistent-hash state.

    * ``algo``    — a name in :data:`ALGORITHMS` (dispatch key),
    * ``n``       — the dynamic size scalar (b-array size for Memento/Jump,
      overall capacity ``a`` for Anchor/Dx),
    * ``arrays``  — named flat int32/uint32 arrays, lengths 128-padded,
    * ``scalars`` — extra dynamic int scalars (e.g. Dx probe bound),
    * ``epoch``   — membership epoch this image snapshots (one per
      remove/add event since construction of the host state),
    * ``packed``  — True when ``arrays`` hold the compact layout of
      :mod:`repro.core.packing` (bit-packed bucket state + narrowed
      words, DESIGN.md §8.2) instead of the full-width dense layout.
      The engine dispatches on this flag, so packed and dense images
      share every public lookup entry point.
    """

    algo: str
    n: int
    arrays: dict[str, np.ndarray] = field(default_factory=dict)
    scalars: dict[str, int] = field(default_factory=dict)
    epoch: int = 0
    packed: bool = False


@dataclass
class ImageDelta:
    """O(changed-words) edit advancing a :class:`DeviceImage` one or more
    epochs.

    * ``algo``       — dispatch key (must match the image's),
    * ``base_epoch`` — epoch of the image the delta applies to,
    * ``epoch``      — epoch of the image after applying,
    * ``n``          — the new dynamic size scalar,
    * ``updates``    — per array name, ``(indices int32[k], values[k])``
      scatter pairs (last-write-wins composition of every event in
      ``(base_epoch, epoch]``),
    * ``scalars``    — new values of the image's dynamic scalars.

    Jump's delta is just the new ``n``; Memento scatters ≤ 1 word per
    event, Anchor 2, Dx 1 (one bitmap word) — versus the O(n) arrays a
    full snapshot re-transfers.
    """

    algo: str
    base_epoch: int
    epoch: int
    n: int
    updates: dict[str, tuple[np.ndarray, np.ndarray]] = field(default_factory=dict)
    scalars: dict[str, int] = field(default_factory=dict)

    @property
    def events(self) -> int:
        return self.epoch - self.base_epoch

    def num_words(self) -> int:
        """Host→device scatter payload in 32-bit words (indices + values)."""
        return sum(2 * len(idx) for idx, _ in self.updates.values())


@dataclass(frozen=True)
class AlgoInfo:
    """One algorithm's registry entry — THE single description every list
    in the repo derives from (engine dispatch, wire ids, image layouts,
    sim churn policy, benchmark grids, the conformance harness).  Adding
    algorithm #N+1 means adding exactly one entry here plus its host class
    and engine body; nothing else enumerates algorithms by hand
    (``tests/test_conformance.py`` scans the sources to enforce that).

    * ``factory``        — ``(initial_nodes, capacity, variant) → instance``
      (lazy-imports the host class, preserving :func:`make_hash` semantics),
    * ``scalars``        — dynamic image scalars, ``n`` always first,
    * ``tables``         — dense-layout table array names,
    * ``required``       — ``n → {table: min length}`` a lookup may gather,
    * ``lifo_only``      — removals restricted to the highest bucket (the
      jump-family contract the sim's victim policies degrade to),
    * ``fixed_capacity`` — overall capacity ``a`` fixed at construction
      (Anchor/Dx); growable algorithms get snapshot headroom instead,
    * ``packed_tables``  — compact-layout table names when the packed
      encoding differs from the dense one (``None`` → same names).
    """

    name: str
    factory: object
    scalars: tuple[str, ...]
    tables: tuple[str, ...]
    required: object
    lifo_only: bool = False
    fixed_capacity: bool = False
    packed_tables: tuple[str, ...] | None = None


def _memento_factory(n0: int, capacity, variant: str):
    from .memento import MementoHash

    return MementoHash(n0, variant=variant)


def _anchor_factory(n0: int, capacity, variant: str):
    from .anchor import AnchorHash

    return AnchorHash(capacity or 10 * n0, n0, variant=variant)


def _dx_factory(n0: int, capacity, variant: str):
    from .dx import DxHash

    return DxHash(capacity or 10 * n0, n0, variant=variant)


def _jump_factory(n0: int, capacity, variant: str):
    from .jump import JumpHash

    return JumpHash(n0, variant=variant)


def _power_factory(n0: int, capacity, variant: str):
    from .power import PowerHash

    return PowerHash(n0, variant=variant)


#: Registry order is the replication wire format (``launch/replicate.py``
#: frame ``algo_id`` = position) — append new algorithms, never reorder.
ALGORITHM_REGISTRY: dict[str, AlgoInfo] = {
    info.name: info for info in (
        AlgoInfo("memento", _memento_factory, ("n",), ("repl",),
                 lambda n: {"repl": n},
                 packed_tables=("state", "slot_b", "slot_c")),
        AlgoInfo("anchor", _anchor_factory, ("n",), ("A", "K"),
                 lambda n: {"A": n, "K": n}, fixed_capacity=True),
        AlgoInfo("dx", _dx_factory, ("n", "max_probes", "fallback"),
                 ("words",), lambda n: {"words": -(-n // 32)},
                 fixed_capacity=True),
        AlgoInfo("jump", _jump_factory, ("n",), (), lambda n: {},
                 lifo_only=True),
        AlgoInfo("power", _power_factory, ("n",), (), lambda n: {},
                 lifo_only=True),
    )
}

#: algorithm names in wire-id order — the ONE list everything derives from
ALGORITHMS: tuple[str, ...] = tuple(ALGORITHM_REGISTRY)

#: per-algorithm device image layout: (scalar names, table array names).
#: ``n`` is always the first scalar; the rest index ``image.scalars``.
IMAGE_LAYOUT: dict[str, tuple[tuple[str, ...], tuple[str, ...]]] = {
    name: (info.scalars, info.tables)
    for name, info in ALGORITHM_REGISTRY.items()
}


def image_scalar_vec(image: DeviceImage) -> list[int]:
    """The image's dynamic scalars in layout order (``n`` first)."""
    names = IMAGE_LAYOUT[image.algo][0]
    return [int(image.n)] + [int(image.scalars[s]) for s in names[1:]]


def required_lengths(algo: str, n: int) -> dict[str, int]:
    """Minimum array lengths a lookup at size ``n`` may gather from."""
    info = ALGORITHM_REGISTRY.get(algo)
    if info is None:
        raise ValueError(f"unknown algo {algo!r}")
    return info.required(n)


def image_fingerprint(image: DeviceImage) -> str:
    """CRC32 hex digest of every word a lookup can observe.

    Hashes ``n``, ``epoch``, the layout scalars, and each array trimmed to
    its :func:`required_lengths` prefix (plus the bucket-indexed ``load``
    overlay words, if present) — capacity padding is excluded, so two
    stores that reached the same epoch through different snapshot/delta
    histories (hence different padded capacities) fingerprint equal iff
    their lookups are bit-identical.  This is the convergence instrument
    for cross-process replication (``launch/replicate.py``) and the sim's
    follower-convergence checker.  Packed images hash their full arrays
    (their layout has no unread padding words beyond the slot area).
    """
    import zlib

    crc = zlib.crc32(np.asarray([image.n, image.epoch], np.int64).tobytes())
    trim = {} if image.packed else required_lengths(image.algo, image.n)
    if "load" in image.arrays:
        trim = dict(trim, load=image.n)
    for name in sorted(image.arrays):
        arr = np.ascontiguousarray(np.asarray(image.arrays[name]))
        if name in trim:
            arr = arr[: trim[name]]
        crc = zlib.crc32(name.encode(), crc)
        crc = zlib.crc32(arr.tobytes(), crc)
    for name in sorted(image.scalars):
        crc = zlib.crc32(f"{name}={int(image.scalars[name])}".encode(), crc)
    return f"{crc & 0xFFFFFFFF:08x}"


def apply_delta(image: DeviceImage, delta: ImageDelta) -> DeviceImage:
    """Host-side (numpy) reference apply: returns a NEW image at
    ``delta.epoch``; ``image`` is left untouched (double-buffer semantics).

    Raises if the delta does not chain onto the image's epoch, or if the
    new ``n`` outgrows an array the delta scatters into (the caller must
    fall back to a fresh snapshot at larger capacity — see
    ``DeviceImageStore``).
    """
    if delta.algo != image.algo:
        raise ValueError(f"delta algo {delta.algo!r} != image {image.algo!r}")
    if delta.base_epoch != image.epoch:
        raise ValueError(
            f"delta base epoch {delta.base_epoch} != image epoch {image.epoch}")
    needed = required_lengths(delta.algo, delta.n)
    arrays = {}
    for name, arr in image.arrays.items():
        if needed.get(name, 0) > arr.shape[0]:
            raise ValueError(f"delta outgrows array {name!r} "
                             f"({arr.shape[0]} < {needed[name]})")
        if name in delta.updates:
            idx, vals = delta.updates[name]
            if len(idx) and int(idx.max()) >= arr.shape[0]:
                raise ValueError(f"delta outgrows array {name!r}")
            arr = arr.copy()
            arr[idx] = vals.astype(arr.dtype)
        arrays[name] = arr
    return DeviceImage(algo=image.algo, n=delta.n, arrays=arrays,
                       scalars=dict(delta.scalars) or dict(image.scalars),
                       epoch=delta.epoch)


class DeltaEmitter:
    """Mixin: epoch counter + bounded per-event delta log (DESIGN.md §3.5).

    Implementations call ``_init_delta_log()`` once and then
    ``_record(updates, n, scalars)`` after every committed membership
    event, where ``updates`` maps array name → {flat index: new value}.
    ``device_delta(since)`` composes the log suffix into one
    :class:`ImageDelta`; when ``since`` predates the log window it returns
    ``None`` — the caller must rebuild from a fresh ``device_image()``.
    """

    _DELTA_LOG_CAP = 8192

    @property
    def image_algo(self) -> str:
        """Dispatch key stamped on emitted images/deltas.  Defaults to
        ``name``; overlay states (e.g. :class:`~repro.core.bounded.
        BoundedLoad`) override it to their *inner* algorithm so the device
        planes dispatch on the real table layout."""
        return self.name

    def _init_delta_log(self) -> None:
        self._epoch = 0
        self._delta_log: list = []

    @property
    def epoch(self) -> int:
        return self._epoch

    def _record(self, updates: dict[str, dict[int, int]], n: int,
                scalars: dict[str, int] | None = None) -> None:
        self._epoch += 1
        self._delta_log.append((self._epoch, updates, n, scalars or {}))
        if len(self._delta_log) > self._DELTA_LOG_CAP:
            # drop the oldest half: amortized O(1) per event, and readers
            # that far behind need a snapshot rebuild anyway
            del self._delta_log[: len(self._delta_log) // 2]

    def device_delta(self, since_epoch: int):
        """Compose every event in ``(since_epoch, epoch]`` into one delta.

        O(events-behind), NOT O(log): log entries hold contiguous epochs
        ending at ``epoch``, so the suffix is an index computation.
        Returns ``None`` when ``since_epoch`` has fallen out of the bounded
        log (snapshot rebuild required).  An up-to-date caller gets an
        empty delta (``events == 0``).
        """
        if since_epoch > self._epoch:
            raise ValueError(f"since_epoch {since_epoch} is in the future "
                             f"(current epoch {self._epoch})")
        if since_epoch < self._epoch - len(self._delta_log):
            return None  # out of the log window
        merged: dict[str, dict[int, int]] = {}
        n = getattr(self, "_image_n")()
        scalars: dict[str, int] = dict(getattr(self, "_image_scalars")())
        start = len(self._delta_log) - (self._epoch - since_epoch)
        for _epoch, updates, _ev_n, _ev_scalars in self._delta_log[start:]:
            for name, edits in updates.items():
                merged.setdefault(name, {}).update(edits)
        updates = {
            name: (np.fromiter(edits.keys(), dtype=np.int32, count=len(edits)),
                   np.fromiter(edits.values(), dtype=np.int64,
                               count=len(edits)).astype(np.int32))
            for name, edits in merged.items()
        }
        return ImageDelta(algo=self.image_algo, base_epoch=since_epoch,
                          epoch=self._epoch, n=n, updates=updates,
                          scalars=scalars)

    def device_delta_range(self, since_epoch: int, until_epoch: int):
        """Compose the events in ``(since_epoch, until_epoch]`` into one
        delta — :meth:`device_delta` generalized to an intermediate target
        epoch, the primitive behind cross-epoch frame batching
        (``launch/replicate.py``): a publisher can chunk a long pending
        range into several ``DELTA_BATCH`` frames without ever composing
        past a chunk boundary.  ``n`` and the dynamic scalars come from the
        log entry AT ``until_epoch`` (every ``_record`` call site commits
        the full post-event scalar set), so the delta lands the follower on
        exactly the epoch-``until`` image.  Returns ``None`` when
        ``since_epoch`` predates the bounded log window.
        """
        if until_epoch > self._epoch:
            raise ValueError(f"until_epoch {until_epoch} is in the future "
                             f"(current epoch {self._epoch})")
        if since_epoch > until_epoch:
            raise ValueError(f"empty range ({since_epoch}, {until_epoch}]")
        if since_epoch < self._epoch - len(self._delta_log):
            return None  # out of the log window
        start = len(self._delta_log) - (self._epoch - since_epoch)
        stop = len(self._delta_log) - (self._epoch - until_epoch)
        if stop == start:  # empty range: report the until-epoch state
            if until_epoch == self._epoch:
                n = getattr(self, "_image_n")()
                scalars = dict(getattr(self, "_image_scalars")())
            elif stop <= 0:  # until sits at the window edge: no entry for it
                return None
            else:
                _e, _u, n, scalars = self._delta_log[stop - 1]
                scalars = dict(scalars)
            return ImageDelta(algo=self.image_algo, base_epoch=since_epoch,
                              epoch=until_epoch, n=n, scalars=scalars)
        merged: dict[str, dict[int, int]] = {}
        for _epoch, updates, _ev_n, _ev_scalars in self._delta_log[start:stop]:
            for name, edits in updates.items():
                merged.setdefault(name, {}).update(edits)
        _e, _u, n, scalars = self._delta_log[stop - 1]
        updates = {
            name: (np.fromiter(edits.keys(), dtype=np.int32, count=len(edits)),
                   np.fromiter(edits.values(), dtype=np.int64,
                               count=len(edits)).astype(np.int32))
            for name, edits in merged.items()
        }
        return ImageDelta(algo=self.image_algo, base_epoch=since_epoch,
                          epoch=until_epoch, n=n, updates=updates,
                          scalars=dict(scalars))

    # -- per-algorithm hooks -------------------------------------------------
    def _image_n(self) -> int:
        raise NotImplementedError

    def _image_scalars(self) -> dict[str, int]:
        return {}


@runtime_checkable
class ConsistentHash(Protocol):
    """Uniform algorithm API: host ops + a flat device image + epoch deltas."""

    name: str

    def lookup(self, key: int) -> int: ...

    def lookup_k(self, key: int, k: int) -> list[int]: ...

    def remove(self, b: int) -> None: ...

    def add(self) -> int: ...

    @property
    def size(self) -> int: ...

    @property
    def working(self) -> int: ...

    def working_set(self) -> set[int]: ...

    def memory_bytes(self) -> int: ...

    def device_image(self, capacity: int | None = None) -> DeviceImage: ...

    @property
    def epoch(self) -> int: ...

    def device_delta(self, since_epoch: int) -> ImageDelta | None: ...


def make_hash(algo: str, initial_node_count: int, *, capacity: int | None = None,
              variant: str = "64"):
    """Factory: algorithm name → ConsistentHash implementation (registry
    dispatch — see :data:`ALGORITHM_REGISTRY`).

    ``capacity`` only applies to the fixed-capacity baselines (Anchor/Dx);
    it defaults to the paper's a/w = 10 compromise.  ``variant="32"`` selects
    the TPU-native arithmetic that the device planes match bit-for-bit.
    """
    info = ALGORITHM_REGISTRY.get(algo)
    if info is None:
        raise ValueError(f"unknown algorithm {algo!r}")
    return info.factory(initial_node_count, capacity, variant)
