"""Compact (packed) device images — minimal-memory table layouts.

The paper's claim is minimal memory *and* optimal lookups; the dense
device images trade that away for simplicity (every word f32-width, the
Memento table Θ(n) even when almost nothing is removed).  This module is
the packed layout (DESIGN.md §8.2) that keeps million-bucket tables
VMEM-resident:

* **memento** — the Dx bitmap precedent applied to Memento: a uint32
  ``state`` bitmap (bit b = 1 ⇔ bucket b working, padding bits working so
  in-capacity growth needs no bitmap writes) plus the Θ(r)
  open-addressing replacement table (``slot_b``, ``slot_c``) in the
  narrowest dtype that holds every bucket id.  The probe sequence is the
  engine's ``packed_reader`` sequence (linear probing from
  ``fmix32(b·GOLDEN32 + 5) & mask``); deletions (bucket restores) leave
  TOMBSTONE slots the reader probes straight past, so epoch deltas edit
  the packed table in place.
* **anchor**  — pure dtype narrowing of A (removal stamps) and K (wrap
  successors): both are bounded by the fixed overall capacity ``a``, so
  int16 suffices for every a ≤ 32 767 (the paper's whole experimental
  range) at exactly half the bytes.
* **dx**      — already a packed bitmap; the words array is shared as-is.
* **jump**, **power** — stateless: nothing to pack.

All planes stay bit-identical to the host oracles: packing changes the
table *encoding*, never the lookup sequence (tests/test_packed.py).
"""
from __future__ import annotations

import numpy as np

from .hashing import GOLDEN32, np_fmix32
from .protocol import (ALGORITHM_REGISTRY, IMAGE_LAYOUT, DeviceImage,
                       ImageDelta, round_up)

#: slot_b sentinels: EMPTY terminates a probe chain, TOMBSTONE (a deleted
#: entry) keeps it alive — readers probe past tombstones, writers reuse them.
EMPTY = -1
TOMBSTONE = -2

#: per-algorithm packed layout: (scalar names, table array names), derived
#: from the registry.  Scalars are identical to the dense layout (the
#: engine's scalar vector must not change); only the table arrays differ —
#: algorithms without a dedicated packed encoding share their dense tables.
PACKED_LAYOUT: dict[str, tuple[tuple[str, ...], tuple[str, ...]]] = {
    name: (info.scalars, info.packed_tables
           if info.packed_tables is not None else info.tables)
    for name, info in ALGORITHM_REGISTRY.items()
}


def image_table_names(image) -> tuple[str, ...]:
    """Table array names of ``image`` in engine operand order."""
    layout = PACKED_LAYOUT if getattr(image, "packed", False) else IMAGE_LAYOUT
    return layout[image.algo][1]


def narrow_dtype(max_value: int) -> np.dtype:
    """Smallest signed dtype holding values in [TOMBSTONE, max_value]."""
    if max_value <= np.iinfo(np.int8).max:
        return np.dtype(np.int8)
    if max_value <= np.iinfo(np.int16).max:
        return np.dtype(np.int16)
    return np.dtype(np.int32)


def image_table_bytes(image) -> int:
    """Device-resident table bytes of an image (the memory the paper's
    minimal-memory claim is about; scalars excluded — O(1) either way)."""
    return sum(int(np.asarray(a).nbytes) for a in image.arrays.values())


# ---------------------------------------------------------------------------
# Memento: bitmap + open-addressing slots
# ---------------------------------------------------------------------------

def _slot_count(r: int, *, headroom: int = 1) -> int:
    """Power-of-two slot count for r removed buckets: load factor ≤ 0.5 at
    ``headroom=1`` (the probe-chain bound of ``packed_reader``), ≤ 0.25 at
    the store's default ``headroom=2`` so delta-driven inserts have room."""
    nslots = 128
    while nslots < 2 * max(headroom, 1) * max(r, 1):
        nslots *= 2
    return nslots


def build_slots(repl, *, nslots: int | None = None,
                dtype=np.int32) -> tuple[np.ndarray, np.ndarray]:
    """Dense repl image → open-addressing (slot_b, slot_c) numpy arrays.

    Insertion is vectorized: each round, every still-unplaced key whose
    current slot is free claims it (first pending key per slot wins); the
    rest advance one slot.  Slots only ever fill, so every slot a key
    skipped is occupied in the final table — the engine's probe loop (scan
    from h0 until hit or empty) finds every key.
    """
    repl = np.asarray(repl)
    removed = np.nonzero(repl >= 0)[0].astype(np.int64)
    r = int(removed.size)
    if nslots is None:
        nslots = _slot_count(r)
    if nslots & (nslots - 1):
        raise ValueError(f"nslots must be a power of two, got {nslots}")
    if nslots < 2 * r:
        raise ValueError(f"load factor > 0.5: {r} entries in {nslots} slots")
    slot_b = np.full((nslots,), EMPTY, dtype)
    slot_c = np.full((nslots,), EMPTY, dtype)
    mask = nslots - 1
    with np.errstate(over="ignore"):
        pos = np_fmix32(removed.astype(np.uint32) * np.uint32(GOLDEN32)
                        + np.uint32(5)).astype(np.int64) & mask
    pending = np.arange(r)
    while pending.size:
        p = pos[pending]
        free = slot_b[p] < 0
        cand = pending[free]
        _, first = np.unique(p[free], return_index=True)
        win = cand[first]
        slot_b[pos[win]] = removed[win].astype(dtype)
        slot_c[pos[win]] = repl[removed[win]].astype(dtype)
        pending = np.setdiff1d(pending, win, assume_unique=True)
        pos[pending] = (pos[pending] + 1) & mask
    return slot_b, slot_c


def _probe_start(b: int, mask: int) -> int:
    with np.errstate(over="ignore"):
        return int(np_fmix32(np.uint32(b) * np.uint32(GOLDEN32)
                             + np.uint32(5))) & mask


def _probe_find(slot_b: np.ndarray, b: int) -> int:
    """Slot index of live entry ``b``, or −1 (probing past tombstones)."""
    nslots = len(slot_b)
    pos = _probe_start(b, nslots - 1)
    for _ in range(nslots):
        sb = int(slot_b[pos])
        if sb == b:
            return pos
        if sb == EMPTY:
            return -1
        pos = (pos + 1) & (nslots - 1)
    return -1


def _probe_upsert(slot_b: np.ndarray, b: int) -> tuple[int, bool]:
    """(slot index, inserted?) for writing entry ``b``: an existing live
    entry is updated in place; otherwise the first tombstone on the probe
    path (else the terminating empty slot) is claimed.  (−1, True) when
    the table has no reusable slot at all."""
    nslots = len(slot_b)
    pos = _probe_start(b, nslots - 1)
    first_tomb = -1
    for _ in range(nslots):
        sb = int(slot_b[pos])
        if sb == b:
            return pos, False
        if sb == TOMBSTONE and first_tomb < 0:
            first_tomb = pos
        if sb == EMPTY:
            return (first_tomb if first_tomb >= 0 else pos), True
        pos = (pos + 1) & (nslots - 1)
    return first_tomb, True  # full scan: every slot live or tombstoned


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def pack_image(image: DeviceImage, *, slot_headroom: int = 1,
               nslots: int | None = None) -> DeviceImage:
    """Dense :class:`DeviceImage` → the packed layout (same epoch, same
    scalars, ``packed=True``).  Arrays NOT in the dense table layout (e.g.
    a bounded-load overlay's ``load`` words) are carried through unchanged.
    ``slot_headroom`` over-provisions the Memento slot table (the store
    packs with headroom 2 so epoch deltas insert without repacking);
    ``nslots`` pins the slot count exactly — the replication publisher's
    targeted catch-up snapshots (``launch/replicate.py``) must rebuild at
    the slot capacity the stream already announced, not a fresh one."""
    if image.packed:
        return image
    arrays: dict[str, np.ndarray] = {}
    if image.algo == "memento":
        repl = np.asarray(image.arrays["repl"])
        pad = repl.shape[0]
        nwords = round_up(-(-pad // 32))
        state = np.full((nwords,), 0xFFFFFFFF, np.uint32)  # all working
        removed = np.nonzero(repl >= 0)[0]
        if removed.size:
            bits = np.zeros((nwords,), np.uint32)
            np.bitwise_or.at(bits, removed >> 5,
                             np.uint32(1) << (removed & 31).astype(np.uint32))
            state &= ~bits
        dtype = narrow_dtype(pad)
        slot_b, slot_c = build_slots(
            repl, nslots=(nslots if nslots is not None
                          else _slot_count(int(removed.size),
                                           headroom=slot_headroom)),
            dtype=dtype)
        arrays = {"state": state, "slot_b": slot_b, "slot_c": slot_c}
    elif image.algo == "anchor":
        A = np.asarray(image.arrays["A"])
        K = np.asarray(image.arrays["K"])
        dtype = narrow_dtype(int(A.shape[0]))  # stamps ≤ a ≤ pad, ids < pad
        arrays = {"A": A.astype(dtype), "K": K.astype(dtype)}
    elif image.algo == "dx":
        arrays = {"words": np.asarray(image.arrays["words"])}
    elif image.algo not in IMAGE_LAYOUT:
        raise ValueError(f"unknown algo {image.algo!r}")
    # remaining algos (jump, power) are stateless: nothing to pack
    handled = set(IMAGE_LAYOUT[image.algo][1])
    for name, arr in image.arrays.items():  # overlays (e.g. "load")
        if name not in handled:
            arrays[name] = np.asarray(arr)
    return DeviceImage(algo=image.algo, n=image.n, arrays=arrays,
                       scalars=dict(image.scalars), epoch=image.epoch,
                       packed=True)


def unpack_image(image: DeviceImage) -> DeviceImage:
    """Packed image → an equivalent dense image (verification path).

    For Memento the dense capacity is the bitmap's (32 × words ≥ the
    original pad — extra padding is working, which dense lookups never
    read below ``n``); Anchor/Dx round-trip bit-exactly.
    """
    if not image.packed:
        return image
    if image.algo == "memento":
        state = np.asarray(image.arrays["state"], np.uint32)
        slot_b = np.asarray(image.arrays["slot_b"])
        slot_c = np.asarray(image.arrays["slot_c"])
        repl = np.full((32 * state.shape[0],), -1, np.int32)
        live = slot_b >= 0
        repl[slot_b[live].astype(np.int64)] = slot_c[live].astype(np.int32)
        bits = (state[np.arange(repl.shape[0]) >> 5]
                >> (np.arange(repl.shape[0]) & 31).astype(np.uint32)) & 1
        if not np.array_equal(bits == 0, repl >= 0):
            raise ValueError("packed image inconsistent: bitmap vs slots")
        arrays = {"repl": repl}
    elif image.algo == "anchor":
        arrays = {"A": np.asarray(image.arrays["A"]).astype(np.int32),
                  "K": np.asarray(image.arrays["K"]).astype(np.int32)}
    elif image.algo == "dx":
        arrays = {"words": np.asarray(image.arrays["words"])}
    elif image.algo in PACKED_LAYOUT:
        arrays = {}  # stateless (jump, power)
    else:
        raise ValueError(f"unknown algo {image.algo!r}")
    handled = set(PACKED_LAYOUT[image.algo][1])
    for name, arr in image.arrays.items():
        if name not in handled:
            arrays[name] = np.asarray(arr)
    return DeviceImage(algo=image.algo, n=image.n, arrays=arrays,
                       scalars=dict(image.scalars), epoch=image.epoch)


# ---------------------------------------------------------------------------
# Epoch deltas on the packed layout
# ---------------------------------------------------------------------------

def packed_delta_updates(mirror: dict[str, np.ndarray], delta: ImageDelta,
                         ) -> dict[str, tuple[np.ndarray, np.ndarray]] | None:
    """Translate a dense :class:`ImageDelta` into scatter updates on the
    packed layout, applying them to the host-side numpy ``mirror`` in
    place.  Returns ``{name: (indices, values)}`` for the device scatter,
    or ``None`` when the packed image must be rebuilt (the Memento slot
    table ran out of room, or live+tombstone fill crossed the 0.5
    load-factor bound that keeps probe chains short).

    Memento's dense ``repl`` scatter becomes bitmap word edits plus slot
    upserts (removals) / tombstones (restores); every other array —
    Anchor A/K, the Dx bitmap, overlays like ``load`` — scatters
    position-for-position with a dtype cast.
    """
    out: dict[str, tuple[np.ndarray, np.ndarray]] = {}
    if delta.algo == "memento" and "repl" in delta.updates:
        idx, vals = delta.updates["repl"]
        state = mirror["state"]
        slot_b, slot_c = mirror["slot_b"], mirror["slot_c"]
        nslots = len(slot_b)
        fill = int(np.count_nonzero(slot_b != EMPTY))  # live + tombstones
        touched_words: dict[int, None] = {}
        touched_slots: dict[int, None] = {}
        for b, v in zip(np.asarray(idx, np.int64), np.asarray(vals, np.int64)):
            b, v = int(b), int(v)
            if b >= 32 * state.shape[0]:
                return None  # outgrew the bitmap: snapshot rebuild
            wi, bit = b >> 5, np.uint32(1) << np.uint32(b & 31)
            if v < 0:  # bucket restored → working: set bit, tombstone slot
                state[wi] |= bit
                pos = _probe_find(slot_b, b)
                if pos >= 0:
                    slot_b[pos] = TOMBSTONE
                    slot_c[pos] = EMPTY
                    touched_slots[pos] = None
            else:      # removed (or replacement redirect): clear bit, upsert
                state[wi] &= ~bit
                pos, inserted = _probe_upsert(slot_b, b)
                if pos < 0:
                    return None  # no reusable slot: repack
                if inserted and int(slot_b[pos]) == EMPTY:
                    fill += 1
                    if 2 * fill > nslots:
                        return None  # probe-chain bound breached: repack
                slot_b[pos] = b
                slot_c[pos] = v
                touched_slots[pos] = None
            touched_words[wi] = None
        if touched_words:
            w = np.fromiter(touched_words, np.int32, len(touched_words))
            out["state"] = (w, state[w].copy())
        if touched_slots:
            s = np.fromiter(touched_slots, np.int32, len(touched_slots))
            out["slot_b"] = (s, slot_b[s].copy())
            out["slot_c"] = (s.copy(), slot_c[s].copy())
    for name, (idx, vals) in delta.updates.items():
        if name == "repl" and delta.algo == "memento":
            continue
        arr = mirror[name]
        idx = np.asarray(idx, np.int32)
        vals = np.asarray(vals)
        if np.issubdtype(arr.dtype, np.signedinteger) and vals.size and \
                int(vals.max(initial=0)) > np.iinfo(arr.dtype).max:
            return None  # value outgrew the narrowed dtype: repack
        cast = vals.astype(arr.dtype)
        arr[idx] = cast
        out[name] = (idx, cast)
    return out
