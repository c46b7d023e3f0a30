"""Cross-process delta replication: one membership owner, N converging
followers (DESIGN.md §9.3, §9.5–§9.7).

MementoHash's control plane is a bounded per-event delta log
(:class:`~repro.core.protocol.DeltaEmitter`).  This module ships that log
across process boundaries: the **leader** process owns the host
``ConsistentHash`` state and publishes each epoch advance as a flat int32
**frame**; **followers** hold no host state at all — just a
:class:`FollowerImageStore` replaying frames into an on-device
:class:`~repro.core.protocol.DeviceImage` with the same out-of-place
scatter code (:func:`repro.kernels.delta_apply.apply_updates`) the leader's
own :class:`~repro.core.DeviceImageStore` runs.  Because both sides apply
identical words in identical epoch order, followers converge to
**bit-identical** images (every word a lookup can gather —
:func:`~repro.core.protocol.image_fingerprint`) and equal epochs.

Frames come in four kinds (DESIGN.md §9.6–§9.7):

  * ``DELTA``           — O(changed-words): scatter (index, value) pairs
    per named array + the new dynamic scalars, epoch-chained onto the
    follower's current epoch;
  * ``DELTA_BATCH``     — the same wire layout covering a RANGE of epochs
    ``(base, epoch]``: the publisher composes N pending epochs
    last-write-wins into one frame, so a 100-event storm burst ships as
    one frame instead of 100;
  * ``SNAPSHOT``        — the full padded dense arrays, sent when the
    delta log no longer covers the published epoch or when growth outruns
    the published capacity (the publisher tracks the capacity it last
    announced, so the leader — not each follower — decides when a
    snapshot is due and every follower takes the same path);
  * ``SNAPSHOT_PACKED`` — the §8.2 compact layout (Memento bitmap + slot
    table, dtype-narrowed Anchor) shipped directly: Θ(n/8 + r) wire bytes
    instead of Θ(4n), and the follower installs it without a dense decode.

Every frame carries a CRC32 integrity word in its header; corrupted or
truncated frames are rejected before any word reaches ``apply_updates``.

Fan-out is topology-pluggable: the flat leader→all broadcast costs the
leader O(F) sends per publish; :class:`TreeTopology` relays verbatim
frames through interior followers (d-ary heap order), dropping the leader
to O(arity) while every node still applies the identical byte stream —
the relay invariant (DESIGN.md §9.5).  A lagging or newly-joined follower
does not stall the stream: :meth:`DeltaPublisher.catchup_frames` serves a
targeted pull — a composed ``DELTA_BATCH`` from the published-frame log
when it still covers the follower's epoch, else a snapshot at the
capacities the stream already announced — landing it exactly on the
published cursor (leader-decides preserved).

Transport is pluggable: :class:`LoopbackChannel` replicates in-process
(the sim driver's follower mode and the unit tests);
:class:`DistributedBroadcast` rides two
``multihost_utils.broadcast_one_to_all`` collectives per round over the
``jax.distributed`` mesh that :func:`repro.launch.mesh.init_distributed`
joins (gloo on CPU, ICI on TPU), and :class:`TreeBroadcast` runs one such
round per interior tree node so real processes relay instead of the
leader paying every send.  Frames are plain ``np.int32`` vectors either
way, so a transport is just "move this vector".
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass

import numpy as np

from repro.core.image_store import delta_fits
from repro.core.packing import PACKED_LAYOUT, pack_image
from repro.core.protocol import (ALGORITHM_REGISTRY, ALGORITHMS,
                                 IMAGE_LAYOUT, DeviceImage, ImageDelta,
                                 image_fingerprint, round_up)
from repro.obs.metrics import default_registry as _default_obs
from repro.obs.metrics import ensure_real

#: frame type tags
KIND_DELTA = 1
KIND_SNAPSHOT = 2
KIND_DELTA_BATCH = 3
KIND_SNAPSHOT_PACKED = 4

_DELTA_KINDS = (KIND_DELTA, KIND_DELTA_BATCH)
_SNAPSHOT_KINDS = (KIND_SNAPSHOT, KIND_SNAPSHOT_PACKED)

_MAGIC = 0x4D454D30  # "MEM0", truncated to int32 range
# wire algo ids ARE registry order — the registry is append-only, so ids
# stay stable across releases (memento=0, anchor=1, dx=2, jump=3, power=4)
_ALGO_IDS = {name: i for i, name in enumerate(ALGORITHMS)}
_ALGO_NAMES = {v: k for k, v in _ALGO_IDS.items()}

#: wire dtype enum for snapshot blocks (packed layouts narrow below int32)
_DTYPES = {0: np.dtype(np.int32), 1: np.dtype(np.uint32),
           2: np.dtype(np.int16), 3: np.dtype(np.int8)}
_DTYPE_IDS = {v: k for k, v in _DTYPES.items()}

#: header flag bits
_FLAG_PACKED = 1


def _array_names(algo: str, packed: bool = False) -> list[str]:
    """Canonical array-name table for the wire: layout tables + the
    bounded-load overlay word array (name_id = position).  Packed frames
    index the packed layout's table names instead of the dense ones."""
    layout = PACKED_LAYOUT if packed else IMAGE_LAYOUT
    return list(layout[algo][1]) + ["load"]


def _scalar_names(algo: str) -> tuple[str, ...]:
    return IMAGE_LAYOUT[algo][0]


# -- wire format --------------------------------------------------------------
# frame = [MAGIC, kind, algo_id, base_epoch, epoch, n, n_extra_scalars,
#          n_blocks, flags, crc, extra_scalars..., blocks...]    (all int32)
# DELTA/DELTA_BATCH block: [name_id, count,  idx[count], vals[count]]
# SNAPSHOT block: [name_id, length, dtype, nwords,  words[nwords]]
#   dtype: 0=i32 1=u32 2=i16 3=i8 (narrow arrays are byte-padded to 4-byte
#   multiples and shipped as int32 words)
# flags: bit 0 = packed layout (name_ids index PACKED_LAYOUT tables).
# crc: CRC32 of the whole frame with the crc word zeroed — the integrity
#   gate decode_frame checks before any word can reach apply_updates.
_HDR = 10
_CRC_SLOT = 9


def stamp_crc(frame: np.ndarray) -> np.ndarray:
    """Stamp the header CRC32 word in place (and return the frame).

    Public so tests that deliberately tamper with header fields can
    re-stamp and reach the check they target instead of tripping the CRC.
    """
    frame[_CRC_SLOT] = 0
    crc = zlib.crc32(frame.tobytes()) & 0xFFFFFFFF
    frame[_CRC_SLOT] = np.array([crc], np.uint32).view(np.int32)[0]
    return frame


def _check_crc(buf: np.ndarray) -> None:
    stored = int(np.array([buf[_CRC_SLOT]], np.int32).view(np.uint32)[0])
    clean = buf.copy()
    clean[_CRC_SLOT] = 0
    if (zlib.crc32(clean.tobytes()) & 0xFFFFFFFF) != stored:
        raise ValueError("frame CRC mismatch (corrupt or truncated frame)")


def _wire_words(arr: np.ndarray) -> tuple[np.ndarray, int, int]:
    """(int32 words, dtype id, element length) for a snapshot block."""
    arr = np.ascontiguousarray(arr)
    dt = _DTYPE_IDS.get(arr.dtype)
    if dt is None:
        raise ValueError(f"array dtype {arr.dtype} has no wire encoding")
    raw = arr.tobytes()
    if len(raw) % 4:
        raw += b"\0" * (4 - len(raw) % 4)
    return np.frombuffer(raw, np.int32), dt, arr.shape[0]


def encode_delta(delta: ImageDelta, *, packed: bool = False) -> np.ndarray:
    """Delta → one flat int32 frame (O(changed-words)).

    A single-epoch delta ships as ``DELTA``; a multi-epoch composition
    (``delta.events > 1``) as ``DELTA_BATCH`` — same block layout, the
    epoch-range header is what tells a follower it may land several epochs
    in one apply.  ``packed=True`` stamps the packed-layout flag: the
    update names index the §8.2 packed tables.
    """
    scal = [int(delta.scalars[s]) for s in _scalar_names(delta.algo)[1:]]
    names = _array_names(delta.algo, packed)
    body: list[np.ndarray] = []
    blocks = 0
    for name, (idx, vals) in sorted(delta.updates.items()):
        if not len(idx):
            continue
        blocks += 1
        head = np.asarray([names.index(name), len(idx)], np.int32)
        body += [head, np.asarray(idx, np.int32),
                 np.asarray(vals).astype(np.int64).astype(np.int32)]
    kind = KIND_DELTA_BATCH if delta.events > 1 else KIND_DELTA
    flags = _FLAG_PACKED if packed else 0
    hdr = np.asarray([_MAGIC, kind, _ALGO_IDS[delta.algo],
                      delta.base_epoch, delta.epoch, delta.n,
                      len(scal), blocks, flags, 0] + scal, np.int32)
    return stamp_crc(np.concatenate([hdr] + body) if body else hdr)


def encode_snapshot(image: DeviceImage) -> np.ndarray:
    """Full (padded) image → one flat int32 frame.

    Dense images ship as ``SNAPSHOT``; packed (§8.2) images ship their
    bitmap + slot tables directly as ``SNAPSHOT_PACKED`` — Θ(n/8 + r)
    wire bytes instead of Θ(4n), installed by a compact follower with no
    dense decode.  Narrow dtypes ride the block dtype tag.
    """
    scal = [int(image.scalars[s]) for s in _scalar_names(image.algo)[1:]]
    names = _array_names(image.algo, image.packed)
    body: list[np.ndarray] = []
    blocks = 0
    for name in sorted(image.arrays):
        words, dt, length = _wire_words(np.asarray(image.arrays[name]))
        blocks += 1
        body += [np.asarray([names.index(name), length, dt, len(words)],
                            np.int32), words]
    kind = KIND_SNAPSHOT_PACKED if image.packed else KIND_SNAPSHOT
    flags = _FLAG_PACKED if image.packed else 0
    hdr = np.asarray([_MAGIC, kind, _ALGO_IDS[image.algo],
                      0, image.epoch, image.n,
                      len(scal), blocks, flags, 0] + scal, np.int32)
    return stamp_crc(np.concatenate([hdr] + body))


@dataclass
class Frame:
    """A decoded (CRC-verified) replication frame."""

    kind: int
    algo: str
    base_epoch: int
    epoch: int
    n: int
    scalars: dict[str, int]
    # DELTA/DELTA_BATCH: name → (idx, vals); SNAPSHOT*: name → np array
    updates: dict
    arrays: dict
    packed: bool = False


def decode_frame(buf: np.ndarray) -> Frame:
    buf = np.asarray(buf, np.int32)
    if len(buf) < _HDR or buf[0] != _MAGIC:
        raise ValueError("not a replication frame")
    _check_crc(buf)
    kind, algo_id = int(buf[1]), int(buf[2])
    if kind not in _DELTA_KINDS + _SNAPSHOT_KINDS:
        raise ValueError(f"unknown frame kind {kind}")
    if algo_id not in _ALGO_NAMES:
        raise ValueError(f"unknown wire algo id {algo_id} "
                         f"(this build knows 0..{len(_ALGO_NAMES) - 1})")
    algo = _ALGO_NAMES[algo_id]
    base_epoch, epoch, n = int(buf[3]), int(buf[4]), int(buf[5])
    n_scal, n_blocks = int(buf[6]), int(buf[7])
    packed = bool(int(buf[8]) & _FLAG_PACKED)
    scal_names = _scalar_names(algo)[1:]
    scalars = {scal_names[i]: int(buf[_HDR + i]) for i in range(n_scal)}
    names = _array_names(algo, packed)
    pos = _HDR + n_scal
    updates: dict = {}
    arrays: dict = {}
    for _ in range(n_blocks):
        if kind in _DELTA_KINDS:
            name, count = names[int(buf[pos])], int(buf[pos + 1])
            pos += 2
            idx = np.array(buf[pos: pos + count], np.int32)
            vals = np.array(buf[pos + count: pos + 2 * count], np.int32)
            pos += 2 * count
            updates[name] = (idx, vals)
        else:
            name, length, dt, nwords = (names[int(buf[pos])],
                                        int(buf[pos + 1]), int(buf[pos + 2]),
                                        int(buf[pos + 3]))
            pos += 4
            dtype = _DTYPES[dt]
            raw = np.ascontiguousarray(buf[pos: pos + nwords]).tobytes()
            arrays[name] = np.frombuffer(
                raw[: length * dtype.itemsize], dtype).copy()
            pos += nwords
    if pos != len(buf):
        raise ValueError(f"trailing bytes in frame ({pos} != {len(buf)})")
    return Frame(kind=kind, algo=algo, base_epoch=base_epoch, epoch=epoch,
                 n=n, scalars=scalars, updates=updates, arrays=arrays,
                 packed=packed)


def _peek_kind(buf) -> int:
    return int(np.asarray(buf, np.int32)[1])


def _peek_base(buf) -> int:
    return int(np.asarray(buf, np.int32)[3])


# -- leader side --------------------------------------------------------------
class DeltaPublisher:
    """Leader-side cursor over the host state's bounded delta log.

    ``frames()`` returns the frames that advance followers from the last
    published epoch to the host's current one.  ``batch_epochs`` shapes
    the stream: 0 (default) composes ALL pending epochs into one
    ``DELTA_BATCH`` per call, 1 ships one ``DELTA`` per epoch (the dense
    per-epoch baseline the wire benchmark measures against), N chunks the
    pending range into batches of ≤ N epochs.  ``packed=True`` keeps a
    host-side numpy mirror of the §8.2 packed arrays and translates every
    dense delta into packed-layout scatters
    (:func:`repro.core.packing.packed_delta_updates`), so snapshots ship
    as ``SNAPSHOT_PACKED`` and deltas edit the follower's packed tables
    directly.  A SNAPSHOT frame goes out on first publish, on log
    overflow, when growth outruns the capacity the last snapshot announced
    (:func:`repro.core.image_store.delta_fits` — the same predicate the
    leader's own store runs), or when the packed mirror can no longer
    absorb a delta in place.  The publisher (not each follower) makes the
    snapshot-vs-delta decision, so every subscriber replays the identical
    frame sequence — the invariant behind bit-identical convergence.

    Published delta frames are remembered in a bounded log of decoded
    payloads; :meth:`catchup_frames` composes that log into ONE targeted
    ``DELTA_BATCH`` for a lagging follower (or falls back to a snapshot at
    the announced capacities), landing it exactly on the published cursor.
    """

    _CATCHUP_LOG_CAP = 512

    def __init__(self, ch, *, headroom: int = 2, batch_epochs: int = 0,
                 packed: bool = False, registry=None):
        self._ch = ch
        self._registry = registry  # None → follow the process default
        self.headroom = max(1, headroom)
        self.batch_epochs = max(0, int(batch_epochs))
        self.packed = bool(packed)
        self._epoch: int | None = None  # nothing published yet
        self._caps: dict[str, int] = {}  # capacities the last snapshot shipped
        self._snap_cap: int | None = None  # dense capacity last announced
        self._mirror: dict[str, np.ndarray] | None = None
        # published-but-not-snapshotted delta payloads, oldest first:
        # (base, epoch, wire updates, n, scalars) — catch-up composition.
        self._log: list[tuple] = []

    def _obs(self):
        """The live telemetry registry (injected, else process default)."""
        return self._registry or _default_obs()

    @property
    def published_epoch(self) -> int | None:  # obs-exempt: pure accessor
        return self._epoch

    @property
    def _algo(self) -> str:
        return getattr(self._ch, "image_algo", self._ch.name)

    def _snapshot_frame(self) -> np.ndarray:
        """Build, announce, and encode a stream snapshot (resets the
        capacity announcement, the packed mirror, and the catch-up log)."""
        algo = self._algo
        if not ALGORITHM_REGISTRY[algo].fixed_capacity:  # growable: same
            cap = round_up(max(self.headroom * self._ch.size, 128))  # headroom
        else:                                            # rule as the store
            cap = None
        img = self._ch.device_image(capacity=cap)
        if self.packed:
            # slot headroom 2 → ≤ 0.25 load factor, same as the leader
            # store's compact mode, so stream deltas insert in place.
            img = pack_image(img, slot_headroom=2)
            self._mirror = {k: np.array(v) for k, v in img.arrays.items()}
        self._caps = {k: int(np.asarray(v).shape[0])
                      for k, v in img.arrays.items()}
        self._snap_cap = cap
        self._epoch = img.epoch
        self._log.clear()
        return encode_snapshot(img)

    def _range_delta(self, base: int, until: int) -> ImageDelta | None:
        if hasattr(self._ch, "device_delta_range"):
            return self._ch.device_delta_range(base, until)
        if until == getattr(self._ch, "epoch", None):  # non-range emitter
            return self._ch.device_delta(base)
        return None

    def frames(self) -> list[np.ndarray]:
        """Frames advancing subscribers to the current host epoch
        (empty when already published)."""
        reg = self._obs()
        with reg.span("repl.encode"):
            out = self._encode_frames()
        if reg.active and out:
            for buf in out:
                kind = ("snapshot" if _peek_kind(buf) in _SNAPSHOT_KINDS
                        else "delta")
                reg.counter("repl.frames_encoded", kind=kind).inc()
        return out

    def _encode_frames(self) -> list[np.ndarray]:
        cur = getattr(self._ch, "epoch", None)
        if self._epoch is None:
            return [self._snapshot_frame()]
        if cur is None or cur == self._epoch:
            return []
        out: list[np.ndarray] = []
        base = self._epoch
        step = self.batch_epochs or (cur - base)
        while base < cur:
            until = min(base + step, cur)
            delta = self._range_delta(base, until)
            if delta is None or not delta_fits(self._caps, delta,
                                               compact=self.packed):
                return [self._snapshot_frame()]  # leader-decides fallback
            if self.packed:
                from repro.core.packing import packed_delta_updates

                updates = packed_delta_updates(self._mirror, delta)
                if updates is None:  # slots/bitmap/dtype outgrown: repack
                    return [self._snapshot_frame()]
                wire = ImageDelta(algo=delta.algo, base_epoch=base,
                                  epoch=until, n=delta.n, updates=updates,
                                  scalars=dict(delta.scalars))
            else:
                wire = delta
            out.append(encode_delta(wire, packed=self.packed))
            self._log.append((base, until, wire.updates, wire.n,
                              dict(wire.scalars)))
            if len(self._log) > self._CATCHUP_LOG_CAP:
                del self._log[: len(self._log) // 2]
            self._epoch = until
            base = until
        return out

    # -- targeted catch-up (the pull path, DESIGN.md §9.7) ---------------------
    def catchup_frames(self, follower_epoch: int) -> list[np.ndarray]:
        """Frames landing a follower at ``follower_epoch`` exactly on the
        published cursor: a composed ``DELTA_BATCH`` when the published
        frame log still chains from that epoch (O(changed-words)), else a
        snapshot at the ANNOUNCED capacities — never a fresh announcement,
        so the stream's in-flight deltas keep fitting on every subscriber.
        """
        if self._epoch is None:
            raise ValueError("nothing published yet (no cursor to target)")
        cur = getattr(self._ch, "epoch", None)
        if cur is not None and cur != self._epoch:
            raise ValueError("pending epochs unpublished: publish the "
                             "stream (frames()) before serving catch-up")
        if follower_epoch == self._epoch:
            return []
        if follower_epoch > self._epoch:
            raise ValueError(f"follower epoch {follower_epoch} is ahead of "
                             f"the published cursor {self._epoch}")
        self._obs().counter("repl.catchup_serves").inc()
        start = next((i for i, ent in enumerate(self._log)
                      if ent[0] == follower_epoch), None)
        if start is not None:
            from repro.kernels.delta_apply import compose_updates

            tail = self._log[start:]
            updates = compose_updates(u for _b, _e, u, _n, _s in tail)
            _b, until, _u, n, scalars = tail[-1]
            wire = ImageDelta(algo=self._algo, base_epoch=follower_epoch,
                              epoch=until, n=n, updates=updates,
                              scalars=dict(scalars))
            return [encode_delta(wire, packed=self.packed)]
        return [self._catchup_snapshot()]

    def _catchup_snapshot(self) -> np.ndarray:
        """Targeted snapshot at the published cursor and announced
        capacities.  Packed mode ships the MIRROR arrays verbatim — the
        slot table's probe layout is history-dependent (tombstones), so a
        fresh repack would diverge from what stream followers hold and
        later slot-position writes would land wrong; the mirror IS the
        byte-exact state every up-to-date follower has."""
        algo = self._algo
        if self.packed and self._mirror is not None:
            ref = self._ch.device_delta(self._epoch)  # empty: n + scalars
            img = DeviceImage(
                algo=algo, n=ref.n,
                arrays={k: v.copy() for k, v in self._mirror.items()},
                scalars=dict(ref.scalars), epoch=self._epoch, packed=True)
            return encode_snapshot(img)
        cap = (None if ALGORITHM_REGISTRY[algo].fixed_capacity
               else self._snap_cap)
        return encode_snapshot(self._ch.device_image(capacity=cap))


# -- follower side ------------------------------------------------------------
class FollowerImageStore:
    """Device image replica driven purely by replication frames.

    Holds no host ``ConsistentHash`` state: SNAPSHOT frames install a fresh
    device image (``SNAPSHOT_PACKED`` installs the §8.2 compact layout with
    no dense decode), DELTA/DELTA_BATCH frames scatter onto the current one
    through the same :func:`~repro.kernels.delta_apply.apply_updates` the
    leader store uses — out of place, with an atomic flip, so in-flight
    lookups stay epoch-consistent here too.

    :meth:`apply_frames` is the drain entry point: it reorders a drained
    batch (snapshot-first, then deltas by base epoch), skips frames made
    stale by a newer snapshot or an earlier catch-up (idempotent
    redelivery), verifies the survivors chain gap-free, and lands them as
    ONE composed scatter — a single device dispatch per drain, however many
    epochs arrived.  ``fingerprint()`` is canonical: packed replicas hash
    their dense equivalent, so a compact follower and a dense leader
    compare equal iff their lookups are bit-identical (the convergence
    gate).

    ``compact`` asserts the expected wire layout (``True`` = packed frames
    only, ``False`` = dense only, ``None`` = accept whatever the leader
    decides).
    """

    def __init__(self, *, plane: str = "jnp", interpret: bool | None = None,
                 compact: bool | None = None, registry=None):
        if plane not in ("jnp", "pallas"):
            raise ValueError(f"unknown plane {plane!r}")
        self.plane = plane
        self.compact = compact
        self._registry = registry  # None → follow the process default
        if interpret is None:
            from repro.kernels.engine import default_interpret
            interpret = default_interpret()
        self._interpret = interpret
        self._front: DeviceImage | None = None
        self.frames_applied = 0
        self.snapshots = 0
        self.deltas = 0
        self.batches = 0        # multi-epoch DELTA_BATCH frames applied
        self.stale_skipped = 0  # idempotently dropped (epoch ≤ current)

    def _obs(self):
        """The live telemetry registry (injected, else process default)."""
        return self._registry or _default_obs()

    @property
    def epoch(self) -> int:  # obs-exempt: pure accessor
        return -1 if self._front is None else self._front.epoch

    def image(self) -> DeviceImage:  # obs-exempt: pure accessor
        if self._front is None:
            raise ValueError("no snapshot received yet")
        return self._front

    def fingerprint(self) -> str:  # obs-exempt: host-side hash, no wire
        """Canonical convergence fingerprint: packed replicas hash their
        dense-equivalent image so dense and compact followers of the same
        leader epoch fingerprint equal."""
        img = self.image()
        if img.packed:
            from repro.core.packing import unpack_image

            img = DeviceImage(
                algo=img.algo, n=img.n,
                arrays={k: np.asarray(v) for k, v in img.arrays.items()},
                scalars=dict(img.scalars), epoch=img.epoch, packed=True)
            img = unpack_image(img)
        return image_fingerprint(img)

    # -- frame application -----------------------------------------------------
    def apply_frame(self, buf: np.ndarray) -> None:
        # obs-exempt: delegates to apply_frames (instrumented)
        self.apply_frames([buf])

    def apply_frames(self, bufs: list[np.ndarray]) -> int:
        """Apply one drained batch of frames; returns how many landed.

        Within the batch: the newest snapshot installs first, deltas are
        reordered by base epoch (transports may interleave streams), frames
        at or below the resulting epoch are skipped as stale, and the
        surviving chain is composed last-write-wins into a single scatter.
        A chain with a REAL gap (a base epoch no frame in the batch
        reaches) still raises — reordering repairs shuffles, not losses.
        """
        reg = self._obs()
        before = (self.snapshots, self.deltas, self.stale_skipped)
        with reg.span("repl.drain", n_frames=len(bufs)):
            applied = self._drain(bufs)
        if reg.active:
            reg.counter("repl.frames_applied").inc(applied)
            reg.counter("repl.snapshots_installed").inc(
                self.snapshots - before[0])
            reg.counter("repl.deltas_applied").inc(self.deltas - before[1])
            reg.counter("repl.stale_skipped").inc(
                self.stale_skipped - before[2])
            reg.gauge("repl.follower_epoch").set(self.epoch)
        return applied

    def _drain(self, bufs: list[np.ndarray]) -> int:
        frames = [decode_frame(b) for b in bufs]
        if not frames:
            return 0
        applied = 0
        snaps = [f for f in frames if f.kind in _SNAPSHOT_KINDS]
        if snaps:
            best = max(snaps, key=lambda f: f.epoch)
            if best.epoch > self.epoch:
                self._install_snapshot(best)
                applied += 1
            self.stale_skipped += len(snaps) - (1 if applied else 0)
        live: list[Frame] = []
        for f in sorted((f for f in frames if f.kind in _DELTA_KINDS),
                        key=lambda f: (f.base_epoch, f.epoch)):
            if f.epoch <= self.epoch:
                self.stale_skipped += 1
                continue
            live.append(f)
        if live:
            applied += self._apply_chain(live)
        self.frames_applied += applied
        return applied

    def _apply_chain(self, live: list[Frame]) -> int:
        if self._front is None:
            raise ValueError("DELTA frame before any SNAPSHOT")
        cur = self._front.epoch
        chain: list[Frame] = []
        for f in live:
            if f.algo != self._front.algo:
                raise ValueError(f"frame algo {f.algo!r} != "
                                 f"{self._front.algo!r}")
            if f.packed != self._front.packed:
                raise ValueError(
                    f"frame layout packed={f.packed} != follower "
                    f"layout packed={self._front.packed}")
            if f.epoch <= cur:  # covered by an earlier frame in this drain
                self.stale_skipped += 1
                continue
            if f.base_epoch > cur:
                raise ValueError(f"frame base epoch {f.base_epoch} != "
                                 f"follower epoch {cur}")
            # base_epoch ≤ cur < epoch: overlap is fine — frames carry
            # ABSOLUTE values, so replaying an already-covered prefix
            # rewrites those words with the frame's (newer) finals.
            chain.append(f)
            cur = f.epoch
        if not chain:
            return 0
        from repro.kernels.delta_apply import apply_updates, compose_updates

        live = chain
        updates = (live[0].updates if len(live) == 1
                   else compose_updates(f.updates for f in live))
        last = live[-1]
        arrays = apply_updates(self._front.arrays, updates,
                               plane=self.plane, interpret=self._interpret)
        self._front = DeviceImage(algo=last.algo, n=last.n, arrays=arrays,
                                  scalars=last.scalars, epoch=last.epoch,
                                  packed=self._front.packed)
        self.deltas += len(live)
        self.batches += sum(f.kind == KIND_DELTA_BATCH for f in live)
        return len(live)

    def _install_snapshot(self, f: Frame) -> None:
        import jax.numpy as jnp

        packed = f.kind == KIND_SNAPSHOT_PACKED
        if self.compact is True and not packed:
            raise ValueError("compact follower received a dense SNAPSHOT")
        if self.compact is False and packed:
            raise ValueError("dense follower received a SNAPSHOT_PACKED")
        self._front = DeviceImage(
            algo=f.algo, n=f.n,
            arrays={k: jnp.asarray(v) for k, v in f.arrays.items()},
            scalars=f.scalars, epoch=f.epoch, packed=packed)
        self.snapshots += 1

    def lookup(self, keys, *, k: int = 1, **kw) -> np.ndarray:
        """Bulk lookup against the replicated image (unified engine —
        packed replicas dispatch the compact reader, no dense decode)."""
        from repro.kernels.engine import engine_lookup

        reg = self._obs()
        out = np.asarray(engine_lookup(keys, self.image(), k=k,
                                       plane=self.plane, **kw))
        if reg.active:
            reg.counter("repl.follower_lookup_keys").inc(int(out.shape[0]))
        return out


# -- topology -----------------------------------------------------------------
class TreeTopology:
    """d-ary relay tree over node ids (heap indexing): node 0 is the
    leader, follower j is node j+1, ``children(i) = a·i+1 … a·i+a``.

    Node-id order IS breadth-first order, which gives the relay invariant
    its schedule: delivering in ascending node id guarantees every
    interior follower has already applied (and can relay verbatim) the
    frames its children are about to receive.  The leader pays O(arity)
    sends per publish instead of the flat broadcast's O(F)."""

    def __init__(self, num_followers: int, *, arity: int = 2):
        if arity < 1:
            raise ValueError("tree arity must be ≥ 1")
        self.arity = int(arity)
        self.nodes = int(num_followers) + 1  # node 0 = leader

    def children(self, node: int) -> list[int]:
        lo = self.arity * node + 1
        return list(range(lo, min(lo + self.arity, self.nodes)))

    def parent(self, node: int) -> int:
        return (node - 1) // self.arity if node > 0 else -1

    def interior(self) -> list[int]:
        """Nodes with children, in BFS (ascending-id) order — the relay
        schedule, and the per-round sources of :class:`TreeBroadcast`."""
        return [i for i in range(self.nodes) if self.children(i)]

    @property
    def depth(self) -> int:
        """Relay hops from the leader to the deepest follower."""
        d, node = 0, self.nodes - 1
        while node > 0:
            node = self.parent(node)
            d += 1
        return d


# -- transports ---------------------------------------------------------------
class LoopbackChannel:
    """In-process frame queue: the sim driver's follower mode and the unit
    tests replicate leader → followers without a second process."""

    def __init__(self):
        self._q: list[np.ndarray] = []

    def publish(self, frames: list[np.ndarray]) -> None:
        self._q.extend(np.array(f, np.int32) for f in frames)

    def drain(self) -> list[np.ndarray]:
        out, self._q = self._q, []
        return out


def _pack_payload(frames: list[np.ndarray]) -> tuple[np.ndarray, np.ndarray]:
    """Frames → (fixed-shape header, length-prefixed concatenated payload)
    — collectives need identical shapes on every process before the
    payload size is known, hence the two-hop scheme."""
    frames = [np.asarray(f, np.int32) for f in frames]
    if frames:
        payload = np.concatenate(
            [np.concatenate([np.asarray([len(f)], np.int32), f])
             for f in frames])
    else:
        payload = np.zeros((0,), np.int32)
    return np.asarray([len(frames), len(payload)], np.int32), payload


def _split_payload(payload: np.ndarray, n_frames: int) -> list[np.ndarray]:
    out, pos = [], 0
    for _ in range(n_frames):
        ln = int(payload[pos])
        out.append(np.array(payload[pos + 1: pos + 1 + ln]))
        pos += 1 + ln
    return out


def _broadcast_round(frames: list[np.ndarray] | None,
                     is_source: bool) -> list[np.ndarray]:
    """One two-hop ``broadcast_one_to_all`` round (header, then payload).
    Collective: EVERY process in the mesh must call this."""
    from jax.experimental import multihost_utils

    hdr, payload = _pack_payload(frames or [])
    hdr = np.asarray(multihost_utils.broadcast_one_to_all(
        hdr, is_source=is_source))
    n_frames, total = int(hdr[0]), int(hdr[1])
    if n_frames == 0:
        return []
    if not is_source:
        payload = np.zeros((total,), np.int32)
    payload = np.asarray(multihost_utils.broadcast_one_to_all(
        payload, is_source=is_source))
    return _split_payload(payload, n_frames)


class DistributedBroadcast:
    """Leader → all-processes frame transport over the ``jax.distributed``
    mesh (:func:`repro.launch.mesh.init_distributed` first; gloo on CPU).

    ``exchange`` is a *collective*: every process calls it each round.  The
    leader passes its frames; followers pass nothing and receive the
    leader's.  Two ``broadcast_one_to_all`` hops per round — a fixed-shape
    header (frame count + total words) then the exactly-sized concatenated
    payload with per-frame length prefixes.
    """

    def __init__(self, *, leader: int = 0):
        self.leader = leader

    def exchange(self, frames: list[np.ndarray] | None = None) -> list[np.ndarray]:
        import jax

        return _broadcast_round(frames,
                                jax.process_index() == self.leader)


class TreeBroadcast:
    """Tree-relay frame transport over the ``jax.distributed`` mesh:
    process id = tree node id (process 0 leads).

    ``exchange`` runs one two-hop broadcast round per INTERIOR tree node,
    in BFS order, with that node's process as the source: the leader seeds
    its children, then each interior follower re-broadcasts the verbatim
    frames it just received to its own children.  Rounds are collectives —
    every process participates in all of them — but only a round's
    children *keep* its frames, so the byte stream each follower applies
    is identical to the flat transport's (the relay invariant over a real
    mesh).  Rounds per publish = interior-node count ≈ F/arity instead of
    the leader serializing F sends."""

    def __init__(self, *, arity: int = 2, leader: int = 0):
        if leader != 0:
            raise ValueError("tree transport pins the leader to process 0")
        self.arity = max(1, int(arity))

    def exchange(self, frames: list[np.ndarray] | None = None) -> list[np.ndarray]:
        import jax

        nproc = int(jax.process_count())
        pid = int(jax.process_index())
        tree = TreeTopology(nproc - 1, arity=self.arity)
        mine = ([np.asarray(f, np.int32) for f in (frames or [])]
                if pid == 0 else [])
        received: list[np.ndarray] = []
        for src in tree.interior():
            got = _broadcast_round(mine if pid == src else [], pid == src)
            if tree.parent(pid) == src:
                received = got
                mine = got  # relay verbatim in this node's own round
        return received


# -- the in-process group -----------------------------------------------------
@dataclass
class WireStats:
    """Cumulative wire accounting for one :class:`ReplicationGroup` — the
    numbers the storm benchmark reads (frames/bytes distinguish what the
    LEADER sent from what crossed any link including relays)."""

    publishes: int = 0
    frames: int = 0          # distinct frames the publisher encoded
    leader_sends: int = 0    # frame transmissions the leader performed
    total_sends: int = 0     # every transmission, relays included
    leader_bytes: int = 0
    total_bytes: int = 0
    catchup_frames: int = 0  # targeted pull-path frames served
    catchup_bytes: int = 0


class ReplicationGroup:
    """Leader + in-process followers in one handle (the sim driver's
    ``followers=`` mode): every ``publish()`` ships the pending epochs to
    each online follower and returns the per-follower convergence lag
    (epochs a follower was behind *before* this round's frames applied).

    ``topology="tree"`` relays frames through interior followers
    (:class:`TreeTopology`) instead of the leader sending to every
    follower; ``batch_epochs``/``packed`` configure the publisher's frame
    stream.  ``set_online(i, False)`` simulates a partitioned follower —
    it misses publishes and, once back, is repaired by the targeted
    catch-up pull (automatically when the next delivery detects the gap,
    or explicitly via :meth:`catch_up`).  ``stats`` accumulates the wire
    accounting; ``last_publish`` snapshots the most recent round for the
    sim driver's per-event metrics."""

    def __init__(self, ch, num_followers: int = 1, *, plane: str = "jnp",
                 headroom: int = 2, topology: str = "flat", arity: int = 2,
                 batch_epochs: int = 0, packed: bool = False, registry=None):
        if topology not in ("flat", "tree"):
            raise ValueError(f"unknown topology {topology!r}")
        # lag/repair gauges are part of the group's public API, so they
        # must record even with telemetry globally off: the injected (or
        # process-default) registry when it is live, else a private one.
        self.telemetry = ensure_real(registry or _default_obs())
        self.publisher = DeltaPublisher(ch, headroom=headroom,
                                        batch_epochs=batch_epochs,
                                        packed=packed,
                                        registry=self.telemetry)
        self.followers = [FollowerImageStore(plane=plane,
                                             compact=packed or None,
                                             registry=self.telemetry)
                          for _ in range(num_followers)]
        self.tree = (TreeTopology(num_followers, arity=arity)
                     if topology == "tree" else None)
        self.topology = topology
        self._online = [True] * num_followers
        self._plane = plane
        self._ch = ch
        self.stats = WireStats()
        self.last_publish = {"frames": 0, "bytes": 0, "leader_sends": 0,
                             "catchup_frames": 0}

    @property
    def depth(self) -> int:  # obs-exempt: pure accessor
        """Fan-out depth: relay hops from leader to the farthest follower."""
        if self.tree is not None:
            return self.tree.depth
        return 1 if self.followers else 0

    def set_online(self, i: int, online: bool = True) -> None:
        """Partition (or heal) follower ``i``: offline followers receive no
        frames — and, in a tree, relay none to their subtree."""
        # obs-exempt: topology toggle, no frames move here
        self._online[i] = bool(online)

    # -- publishing ------------------------------------------------------------
    def publish(self) -> list[int]:
        reg = self.telemetry
        before = (self.stats.frames, self.stats.total_bytes,
                  self.stats.leader_sends, self.stats.catchup_frames)
        with reg.span("repl.publish", topology=self.topology):
            frames = self.publisher.frames()
            target = getattr(self._ch, "epoch", 0)
            lags = [max(0, target - max(f.epoch, 0))
                    for f in self.followers]
            if frames:
                self.stats.publishes += 1
                self.stats.frames += len(frames)
                with reg.span("repl.relay", n_frames=len(frames)):
                    if self.tree is None:
                        self._deliver_flat(frames)
                    else:
                        self._deliver_tree(frames)
        self.last_publish = {
            "frames": self.stats.frames - before[0],
            "bytes": self.stats.total_bytes - before[1],
            "leader_sends": self.stats.leader_sends - before[2],
            "catchup_frames": self.stats.catchup_frames - before[3],
        }
        if frames:
            reg.counter("repl.publishes").inc()
        reg.counter("repl.wire_frames").inc(self.last_publish["frames"])
        reg.counter("repl.wire_bytes").inc(self.last_publish["bytes"])
        reg.counter("repl.leader_sends").inc(
            self.last_publish["leader_sends"])
        for i, lag in enumerate(lags):
            reg.gauge("repl.follower_lag", follower=i).set(lag)
        reg.gauge("repl.follower_lag_max").set(max(lags, default=0))
        reg.sink.emit("publish", **self.last_publish,
                      epoch=self.publisher.published_epoch,
                      lag_max=max(lags, default=0))
        return lags

    @staticmethod
    def _nbytes(frames: list[np.ndarray]) -> int:
        return sum(4 * len(f) for f in frames)

    def _deliver_flat(self, frames: list[np.ndarray]) -> None:
        nbytes = self._nbytes(frames)
        for i in range(len(self.followers)):
            if not self._online[i]:
                continue
            self.stats.leader_sends += len(frames)
            self.stats.total_sends += len(frames)
            self.stats.leader_bytes += nbytes
            self.stats.total_bytes += nbytes
            self._apply(i, frames)

    def _deliver_tree(self, frames: list[np.ndarray]) -> None:
        nbytes = self._nbytes(frames)
        inbox: dict[int, list[np.ndarray]] = {}
        for c in self.tree.children(0):  # the only sends the leader pays
            inbox[c] = frames
            self.stats.leader_sends += len(frames)
            self.stats.total_sends += len(frames)
            self.stats.leader_bytes += nbytes
            self.stats.total_bytes += nbytes
        for node in range(1, self.tree.nodes):  # BFS: parents before kids
            got = inbox.pop(node, None)
            if got is None:
                continue
            i = node - 1
            if not self._online[i]:
                continue  # partitioned: subtree misses this round too
            self._apply(i, got)
            for c in self.tree.children(node):  # relay verbatim
                inbox[c] = got
                self.stats.total_sends += len(got)
                self.stats.total_bytes += nbytes

    def _apply(self, i: int, frames: list[np.ndarray]) -> None:
        """Deliver one round to follower ``i``; a follower that the round
        cannot chain onto (it missed earlier publishes) is first repaired
        through the targeted catch-up pull — after which the round's own
        frames skip as stale, keeping delivery idempotent."""
        fol = self.followers[i]
        batch = list(frames)
        has_snap = any(_peek_kind(b) in _SNAPSHOT_KINDS for b in batch)
        bases = [_peek_base(b) for b in batch
                 if _peek_kind(b) in _DELTA_KINDS]
        if not has_snap and bases and min(bases) > fol.epoch:
            batch = self._pull_catchup(fol.epoch) + batch
        with self.telemetry.span("repl.apply", follower=i):
            fol.apply_frames(batch)

    def _pull_catchup(self, epoch: int) -> list[np.ndarray]:
        cf = self.publisher.catchup_frames(epoch)
        nbytes = self._nbytes(cf)
        self.stats.catchup_frames += len(cf)
        self.stats.catchup_bytes += nbytes
        self.stats.leader_sends += len(cf)
        self.stats.total_sends += len(cf)
        self.stats.leader_bytes += nbytes
        self.stats.total_bytes += nbytes
        self.telemetry.counter("repl.catchup_repairs").inc()
        self.telemetry.counter("repl.catchup_frames").inc(len(cf))
        self.telemetry.counter("repl.catchup_bytes").inc(nbytes)
        return cf

    # -- the pull path ---------------------------------------------------------
    def catch_up(self, i: int) -> int:
        """Explicitly repair follower ``i`` to the published cursor via the
        targeted pull; returns the number of catch-up frames served."""
        # obs-exempt: delegates to publish/_pull_catchup (instrumented)
        self.publish()  # the stream ships to everyone first (leader-decides)
        fol = self.followers[i]
        if fol.epoch == self.publisher.published_epoch:
            return 0
        cf = self._pull_catchup(fol.epoch)
        fol.apply_frames(cf)
        return len(cf)

    def attach_follower(self) -> FollowerImageStore:
        """Join a NEW follower mid-stream: it pulls a targeted catch-up at
        its own (empty) base instead of stalling until the next publish."""
        self.publish()
        fol = FollowerImageStore(plane=self._plane,
                                 compact=self.publisher.packed or None,
                                 registry=self.telemetry)
        cf = self._pull_catchup(fol.epoch)
        fol.apply_frames(cf)
        self.followers.append(fol)
        self._online.append(True)
        self.telemetry.counter("repl.followers_attached").inc()
        return fol

    def converged(self, leader_image: DeviceImage) -> bool:
        # obs-exempt: host-side fingerprint comparison, no wire
        want = image_fingerprint(leader_image)
        return all(f.epoch == leader_image.epoch and f.fingerprint() == want
                   for f in self.followers)
