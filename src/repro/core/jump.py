"""JumpHash (Lamping & Veach, 2014) — the paper's core engine.

Two variants (DESIGN.md §3):

* ``jump64``: the paper-faithful 64-bit LCG implementation (the exact
  pseudo-code from arXiv:1406.2294).
* ``jump32``: the TPU-native variant.  Each step's uniform variate comes from
  a murmur3-mixed (key, step) hash and the divide runs in float32, correctly
  rounded (IEEE).  The device data plane matches it bit-for-bit; a TPU's
  float32 divide is not correctly rounded, so the device settles each step
  on integers (``kernels/primitives.floor_rn_quotient``).
"""
from __future__ import annotations

import numpy as np

from .hashing import GOLDEN32, LCG_MULT, MASK32, MASK64, np_fmix32, fmix32
from .protocol import DeltaEmitter, DeviceImage, ReplicatedLookup


def jump64(key: int, num_buckets: int) -> int:
    """Faithful JumpHash: O(ln n), stateless, no memory access."""
    if num_buckets <= 0:
        raise ValueError("num_buckets must be positive")
    key &= MASK64
    b, j = -1, 0
    while j < num_buckets:
        b = j
        key = (key * LCG_MULT + 1) & MASK64
        j = int(float(b + 1) * (float(1 << 31) / float((key >> 33) + 1)))
    return b


def jump32(key: int, num_buckets: int) -> int:
    """TPU-native JumpHash variant (scalar reference; see np_jump32)."""
    out = np_jump32(np.asarray([key & MASK32], dtype=np.uint32), num_buckets)
    return int(out[0])


def _step_u24(keys: np.ndarray, step: int | np.ndarray) -> np.ndarray:
    """Per-(key, step) uniform 24-bit variate (exactly representable in f32)."""
    step = np.asarray(step, dtype=np.uint32)
    with np.errstate(over="ignore"):
        h = np_fmix32(keys ^ (step * np.uint32(GOLDEN32) + np.uint32(0x2545F491)))
    return (h >> np.uint32(8)).astype(np.uint32)


def np_jump32(keys: np.ndarray, num_buckets: int) -> np.ndarray:
    """Vectorized TPU-native jump over a uint32 key array.

    State machine identical to jump64's: ``b ← j; j ← floor((b+1)/r)`` with
    ``r`` uniform in (0, 1], iterated while ``j < n``.  ``r`` is quantized to
    24 bits so every intermediate is exact in f32.
    """
    if num_buckets <= 0:
        raise ValueError("num_buckets must be positive")
    keys = keys.astype(np.uint32)
    n = np.float32(num_buckets)
    b = np.zeros(keys.shape, dtype=np.int32)
    j = np.zeros(keys.shape, dtype=np.float32)
    i = 0
    active = j < n
    while active.any():
        b = np.where(active, j.astype(np.int32), b)
        u = _step_u24(keys, i)
        r = (u.astype(np.float32) + np.float32(1.0)) * np.float32(2.0 ** -24)
        jn = np.float32(1.0) * (b.astype(np.float32) + np.float32(1.0)) / r
        jn = np.minimum(np.floor(jn), n)  # clamp: anything ≥ n terminates
        j = np.where(active, jn, j)
        active = j < n
        i += 1
        if i > 256:  # 24-bit r ⇒ ≤ ~2^24 expansion/step; unreachable in practice
            raise RuntimeError("jump32 failed to terminate")
    return b


class JumpHash(ReplicatedLookup, DeltaEmitter):
    """Stateful wrapper exposing the uniform engine API (LIFO-only resizes)."""

    name = "jump"

    def __init__(self, initial_node_count: int, variant: str = "64"):
        if initial_node_count <= 0:
            raise ValueError("initial_node_count must be positive")
        if variant == "64":
            self._fn = jump64
        elif variant == "32":
            self._fn = jump32
        else:
            raise ValueError(f"unknown variant {variant!r}")
        self.variant = variant
        self.n = initial_node_count
        self._init_delta_log()

    def lookup(self, key: int) -> int:
        return self._fn(key, self.n)

    # convenience for tests/benchmarks (mirrors MementoHash.lookup_trace)
    def lookup_trace(self, key: int) -> tuple[int, int, int]:
        """Jump has no replacement walk: the jump chain is internal to
        ``jump32``/``jump64``, so the step counts are reported as 0."""
        return self.lookup(key), 0, 0

    def add(self) -> int:
        self.n += 1
        self._record({}, self.n)  # the whole delta is the new n
        return self.n - 1

    def remove(self, b: int) -> None:
        if b != self.n - 1:
            raise ValueError("JumpHash only supports LIFO removals")
        if self.n == 1:
            raise ValueError("cannot remove the last bucket")
        self.n -= 1
        self._record({}, self.n)

    def _image_n(self) -> int:
        return self.n

    @property
    def size(self) -> int:
        return self.n

    @property
    def working(self) -> int:
        return self.n

    def working_set(self) -> set[int]:
        return set(range(self.n))

    def memory_bytes(self) -> int:
        return 8  # a single counter

    def device_image(self, capacity: int | None = None) -> DeviceImage:
        """Stateless: the image is just the dynamic n (lookup = jump32)."""
        return DeviceImage(algo=self.name, n=self.n, epoch=self._epoch)
