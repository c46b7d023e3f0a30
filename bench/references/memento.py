"""Plain numpy reference of Memento, independent of ``src/``.

The harness finds it by the configuration's ``algo`` and uses
``key_to_u32`` and :class:`Reference`.

It restates, from the paper and the system's documented 32-bit hashing,
the semantics the timed path must reproduce bit for bit:

* ``key_to_u32`` — a 64-bit session id folded to 32 bits (low word XOR
  high word) and mixed by the murmur3 32-bit finalizer;
* ``jump32`` — JumpHash (Lamping & Veach) with a 24-bit uniform variate
  per (key, step) and the step ``j = floor((b + 1) / r)`` divided in
  IEEE float32, correctly rounded, as numpy on the host does it;
* :class:`Reference` — MementoHash's remove and lookup (Coluzzi et al.,
  arXiv:2306.09783, Algs. 2-4) over the replacement set ``R``.

The lookup is Alg. 4 per key, written over arrays so that a million keys
take seconds; lanes that have settled leave the loop, which changes no
lane's answer.  Nothing here imports the program or reads what it made.
"""
from __future__ import annotations

import numpy as np

_C1, _C2 = 0x85EBCA6B, 0xC2B2AE35
GOLDEN32 = 0x9E3779B1
STEP_SALT = 0x2545F491
_U32 = np.uint32


def fmix32(h) -> np.ndarray:
    """Murmur3's 32-bit finalizer, wrapping uint32 arithmetic."""
    h = np.asarray(h, dtype=_U32).copy()
    with np.errstate(over="ignore"):
        h ^= h >> _U32(16)
        h *= _U32(_C1)
        h ^= h >> _U32(13)
        h *= _U32(_C2)
        h ^= h >> _U32(16)
    return h


def key_to_u32(ids) -> np.ndarray:
    """64-bit session ids → 32-bit keys: fmix32(low word ^ high word)."""
    k = np.asarray(ids, dtype=np.uint64)
    return fmix32(((k & np.uint64(0xFFFFFFFF)) ^ (k >> np.uint64(32))).astype(_U32))


def hash2(keys, seed) -> np.ndarray:
    """Alg. 4's ``hash(key, b)``: fmix32(key ^ fmix32(seed * GOLDEN32 + 1))."""
    seed = np.asarray(seed, dtype=_U32)
    with np.errstate(over="ignore"):
        s = fmix32(seed * _U32(GOLDEN32) + _U32(1))
    return fmix32(np.asarray(keys, dtype=_U32) ^ s)


def _step_u24(keys, step: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        salt = _U32((step * GOLDEN32 + STEP_SALT) & 0xFFFFFFFF)
    return fmix32(keys ^ salt) >> _U32(8)


def jump32(keys, n: int) -> np.ndarray:
    """JumpHash over uint32 keys into ``[0, n)``, ``n <= 2**24``:
    ``b <- j; j <- floor((b + 1) / r)`` while ``j < n``, with
    ``r = (u + 1) * 2**-24`` and the quotient in float32."""
    keys = np.asarray(keys, dtype=_U32)
    b = np.zeros(keys.shape, np.int32)
    j = np.zeros(keys.shape, np.int64)
    live = np.arange(keys.size)
    step = 0
    while live.size:
        b[live] = j[live]
        r = (_step_u24(keys[live], step).astype(np.float32) + np.float32(1.0)) \
            * np.float32(2.0 ** -24)
        q = (b[live].astype(np.float32) + np.float32(1.0)) / r
        j[live] = np.minimum(np.floor(q), np.float32(n)).astype(np.int64)
        live = live[j[live] < n]
        step += 1
    return b


class Reference:
    """MementoHash state ``<n, R, l>`` (Alg. 1) with Algs. 2 and 4."""

    def __init__(self, n: int):
        if not 0 < n <= 1 << 24:
            raise ValueError(f"n={n} outside (0, 2**24]")
        self.n = n
        self.l = n
        self.R: dict[int, tuple[int, int]] = {}   # b -> (c, p)
        self._c = np.full(n, -1, np.int64)        # c of removed b, else -1

    @property
    def working(self) -> int:
        return self.n - len(self.R)

    def remove(self, b: int) -> None:
        """Alg. 2: a removal of the last bucket with R empty shrinks n;
        any other records ``b -> (w - 1, l)`` and sets ``l = b``."""
        b = int(b)
        if not 0 <= b < self.n or b in self.R:
            raise ValueError(f"bucket {b} is not working")
        if self.working == 1:
            raise ValueError("cannot remove the last working bucket")
        if b == self.n - 1 and not self.R:
            self.n -= 1
            self.l = self.n
            return
        self.R[b] = (self.working - 1, self.l)
        self._c[b] = self.working  # the new w, after the insert above
        self.l = b

    def lookup(self, keys) -> np.ndarray:
        """Alg. 4 for every uint32 key."""
        keys = np.asarray(keys, dtype=_U32)
        c = self._c
        out = jump32(keys, self.n)
        live = np.flatnonzero(c[out] >= 0)        # keys on a removed bucket
        while live.size:
            b = out[live]
            wb = c[b]
            d = (hash2(keys[live], b) % wb.astype(_U32)).astype(np.int64)
            follow = c[d] >= wb                   # follow while c[d] >= w_b
            while follow.any():
                d = np.where(follow, c[d], d)
                follow = c[d] >= wb
            out[live] = d
            live = live[c[d] >= 0]
        return out
