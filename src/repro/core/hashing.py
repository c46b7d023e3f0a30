"""Shared hash primitives for the consistent-hashing control plane.

Two families are provided (see DESIGN.md §3 "Hardware adaptation"):

* 64-bit: paper-faithful (JumpHash's LCG, murmur-style fmix64).  Used by the
  host control plane and the paper-reproduction benchmarks.
* 32-bit: TPU-native (murmur3 fmix32 mixing).  The device data plane
  (``core/jax_lookup.py`` and ``kernels/``) uses *exactly* this arithmetic;
  the numpy implementations here are bit-identical so host and device agree.

All scalar functions take/return python ints; ``np_*`` variants are
vectorized over ``np.uint32`` arrays with wrap-around semantics.
"""
from __future__ import annotations

import numpy as np

MASK32 = 0xFFFFFFFF
MASK64 = 0xFFFFFFFFFFFFFFFF

# Knuth / murmur constants.
LCG_MULT = 2862933555777941757          # JumpHash's 64-bit LCG multiplier
GOLDEN32 = 0x9E3779B1
GOLDEN64 = 0x9E3779B97F4A7C15
_C1_32 = 0x85EBCA6B
_C2_32 = 0xC2B2AE35
_C1_64 = 0xFF51AFD7ED558CCD
_C2_64 = 0xC4CEB9FE1A85EC53


# ---------------------------------------------------------------------------
# Scalar (python int) versions — host control plane.
# ---------------------------------------------------------------------------

def fmix64(h: int) -> int:
    """Murmur3 64-bit finalizer: a high-quality uniform mixer."""
    h &= MASK64
    h ^= h >> 33
    h = (h * _C1_64) & MASK64
    h ^= h >> 33
    h = (h * _C2_64) & MASK64
    h ^= h >> 33
    return h


def fmix32(h: int) -> int:
    """Murmur3 32-bit finalizer."""
    h &= MASK32
    h ^= h >> 16
    h = (h * _C1_32) & MASK32
    h ^= h >> 13
    h = (h * _C2_32) & MASK32
    h ^= h >> 16
    return h


def hash2_64(key: int, seed: int) -> int:
    """Uniform hash of (key, seed) — the ``hash(key, b)`` of paper Alg. 4."""
    return fmix64((key & MASK64) ^ fmix64(seed * GOLDEN64 + 1))


def hash2_32(key: int, seed: int) -> int:
    """32-bit (key, seed) hash; bit-identical to the device plane."""
    return fmix32((key & MASK32) ^ fmix32((seed * GOLDEN32 + 1) & MASK32))


def key_to_u64(key) -> int:
    """Map an arbitrary key (int/str/bytes) to uint64."""
    if isinstance(key, (int, np.integer)):
        return int(key) & MASK64
    if isinstance(key, str):
        key = key.encode("utf-8")
    if isinstance(key, bytes):
        h = 0xCBF29CE484222325  # FNV-1a 64
        for byte in key:
            h = ((h ^ byte) * 0x100000001B3) & MASK64
        return h
    raise TypeError(f"unsupported key type: {type(key)!r}")


def key_to_u32(key) -> int:
    return fmix32(key_to_u64(key) & MASK32 ^ (key_to_u64(key) >> 32))


# ---------------------------------------------------------------------------
# Vectorized numpy versions — bit-identical to the jnp/Pallas data plane.
# ---------------------------------------------------------------------------

def np_fmix32(h: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = h.astype(np.uint32)
        h ^= h >> np.uint32(16)
        h = (h * np.uint32(_C1_32)).astype(np.uint32)
        h ^= h >> np.uint32(13)
        h = (h * np.uint32(_C2_32)).astype(np.uint32)
        h ^= h >> np.uint32(16)
    return h


def _fmix32_inplace(h: np.ndarray, scratch: np.ndarray) -> None:
    """`np_fmix32` on a uint32 array in place; ``scratch`` is a same-size
    uint32 buffer for the shifts."""
    np.right_shift(h, np.uint32(16), out=scratch)
    h ^= scratch
    np.multiply(h, np.uint32(_C1_32), out=h)
    np.right_shift(h, np.uint32(13), out=scratch)
    h ^= scratch
    np.multiply(h, np.uint32(_C2_32), out=h)
    np.right_shift(h, np.uint32(16), out=scratch)
    h ^= scratch


# Keys hashed per pass of `np_key_to_u32`: a block's ids (512 KB) and its
# words (256 KB) stay in cache while every step of the hash runs over them.
_KEY_BLOCK = 1 << 16


def np_key_to_u32(keys: np.ndarray) -> np.ndarray:
    """Vectorized `key_to_u32` for integer keys (matches the scalar path).

    Native, C-contiguous 8-byte ids are read in place as pairs of uint32
    words; any other input is first converted once to uint64. The fold
    (low word ^ high word, the same in either byte order) and `fmix32` run
    block by block on the output, so each block is hashed while it sits
    in cache. ``keys`` is never written.
    """
    k = np.asarray(keys)
    # a dtype equals np.uint64 / np.int64 only in native byte order
    if not (k.dtype in (np.uint64, np.int64) and k.flags.c_contiguous):
        k = k.astype(np.uint64, order="C")
    words = k.reshape(-1).view(np.uint32)
    n = k.size
    out = np.empty(n, np.uint32)
    scratch = np.empty(min(n, _KEY_BLOCK), np.uint32)
    for s in range(0, n, _KEY_BLOCK):
        e = min(s + _KEY_BLOCK, n)
        h = out[s:e]
        np.bitwise_xor(words[2 * s:2 * e:2], words[2 * s + 1:2 * e:2], out=h)
        _fmix32_inplace(h, scratch[:e - s])
    return out.reshape(k.shape)


def np_hash2_32(keys: np.ndarray, seed: np.ndarray | int) -> np.ndarray:
    seed = np.asarray(seed, dtype=np.uint32)
    with np.errstate(over="ignore"):
        s = np_fmix32(seed * np.uint32(GOLDEN32) + np.uint32(1))
        return np_fmix32(keys.astype(np.uint32) ^ s)
