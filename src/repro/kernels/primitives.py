"""Shared device-plane hash primitives (jnp, Pallas-safe).

One implementation of the TPU-native 32-bit arithmetic (DESIGN.md §3.1),
consumed by BOTH the pure-jnp oracles (``core/jax_lookup.py``) and the
Pallas engine (``kernels/engine.py``).  Everything here runs under plain
jit and in interpret-mode Pallas; Mosaic (Pallas on TPU) compiles
``fmix32``/``hash2``/``jump32``/``power32`` but not ``gather1d``, a 1-D
gather Mosaic has no lowering for (DESIGN.md §6).  Per-lane loop carries
start from ``*_like(keys)`` so that, under ``jax.shard_map``, they carry
the same varying mesh axes as the keys that update them.

Bit-identical to the numpy/scalar host plane in ``core/hashing.py`` and
``core/jump.py``: murmur3 fmix32 mixing, 24-bit uniform variates, the
host's correctly rounded f32 jump step decided on integers.  Constants
are imported from ``core/hashing`` so there is a single definition in
the repo.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.hashing import _C1_32, _C2_32, GOLDEN32

_U = jnp.uint32

#: per-step salt of the jump32 variate stream (matches ``core/jump._step_u24``)
STEP_SALT = 0x2545F491


def fmix32(h):
    """Murmur3 32-bit finalizer over a uint32 array (or traced scalar)."""
    h = jnp.asarray(h).astype(_U)
    h ^= h >> _U(16)
    h = h * _U(_C1_32)
    h ^= h >> _U(13)
    h = h * _U(_C2_32)
    h ^= h >> _U(16)
    return h


def hash2(keys, seed):
    """(key, seed) hash — paper Alg. 4's ``hash(k, b)``; seed may be a traced
    scalar (e.g. the Dx probe index) or an array (e.g. bucket ids)."""
    s = fmix32(jnp.asarray(seed).astype(_U) * _U(GOLDEN32) + _U(1))
    return fmix32(jnp.asarray(keys).astype(_U) ^ s)


def step_u24(keys, step):
    """Per-(key, step) uniform 24-bit variate — exactly representable in f32."""
    s = jnp.asarray(step).astype(_U)
    h = fmix32(jnp.asarray(keys).astype(_U) ^ (s * _U(GOLDEN32) + _U(STEP_SALT)))
    return h >> _U(8)


def floor_rn_quotient(x, den, approx, n):
    """``min(⌊fl32(x·2²⁴ / den)⌋, n)`` exactly, from any ``approx`` of the
    quotient within a few ulps.

    The host's jump step divides in float32 and rounds to nearest
    (IEEE); a TPU's float32 divide is not correctly rounded, so the device
    cannot simply repeat it.  Here the float quotient is only a first
    guess: the exact integer quotient and remainder follow from 32-bit
    wrapping arithmetic (the remainder is small, so its low 32 bits are
    the whole of it), and round-to-nearest is then decided on integers.
    ``x`` ≥ 1 and ``den`` ∈ [1, 2²⁴] are int32 arrays, ``n`` ≤ 2²⁴ the
    clamp (``int32``).
    """
    i32 = jnp.int32
    nf = n.astype(jnp.float32)
    big = approx >= nf + jnp.float32(16.0)  # q > n whatever the error
    q = jnp.floor(jnp.minimum(approx, nf + jnp.float32(16.0))).astype(i32)
    # remainder of x·2²⁴ − q·den, exact mod 2³² and |rem| < 2³¹
    rem = jax.lax.bitcast_convert_type(
        (x.astype(_U) << _U(24)) - q.astype(_U) * den.astype(_U), i32)
    t = jnp.floor(rem.astype(jnp.float32) / den.astype(jnp.float32)).astype(i32)
    q, rem = q + t, rem - t * den
    low = rem < 0
    q, rem = jnp.where(low, q - 1, q), jnp.where(low, rem + den, rem)
    high = rem >= den
    q, rem = jnp.where(high, q + 1, q), jnp.where(high, rem - den, rem)
    # q = ⌊x·2²⁴/den⌋ ≥ 1, rem ∈ [0, den).  fl32 rounds up to q+1 iff the
    # gap (den − rem)/den is below half an ulp, 2^(e−24) for q ∈ [2^e, 2^(e+1)).
    # It is never exactly half an ulp: a tie needs x·2^(48−e) = m·den with m
    # odd, so 2^(v₂(x) + 48 − e) divides den: den ≥ 2²⁵ for e ≤ 23.  No
    # tie-breaking rule is needed (q ≥ 2²⁴ ≥ n is clamped anyway).
    e = (jax.lax.bitcast_convert_type(
        jnp.maximum(q, 1).astype(jnp.float32), i32) >> 23) - 127
    up = den - rem <= ((den - 1) >> jnp.clip(24 - e, 1, 24))
    return jnp.where(big, n, jnp.minimum(q + up.astype(i32), n))


def jump32(keys, n):
    """Vectorized TPU-native JumpHash: keys uint32 [...], n a dynamic scalar
    (n ≤ 2²⁴, where every float32 step of the host reference is exact).

    State machine identical to the 64-bit original: ``b ← j; j ← ⌊(b+1)/r⌋``
    with ``r = (u+1)·2⁻²⁴`` uniform in (0, 1], iterated while ``j < n``;
    lane-synchronous (a block settles in max-over-lanes steps, E ≈ ln n).
    Each step equals the host's float32 step bit for bit on any backend
    (:func:`floor_rn_quotient`).

    Step 0 (every lane active at ``b = 0``) is peeled out of the loop, so
    the carry ``j`` enters it already lane-varying: Mosaic cannot lay out
    a loop carry that starts as a splat constant and is then updated from
    the keys.
    """
    keys = jnp.asarray(keys).astype(_U)
    n = jnp.asarray(n).astype(jnp.int32)

    def step(b, i):
        # u < 2^24 fits int32, and Mosaic casts only signed ints to float
        den = step_u24(keys, i).astype(jnp.int32) + 1
        x = b + 1
        approx = x.astype(jnp.float32) / (den.astype(jnp.float32)
                                          * jnp.float32(2.0 ** -24))
        return floor_rn_quotient(x, den, approx, n)

    def cond(state):
        _, j, _ = state
        return jnp.any(j < n)

    def body(state):
        b, j, i = state
        active = j < n
        b = jnp.where(active, j, b)
        return b, jnp.where(active, step(b, i), j), i + jnp.int32(1)

    b0 = jnp.zeros_like(keys, jnp.int32)
    b, _, _ = jax.lax.while_loop(cond, body,
                                 (b0, step(b0, jnp.int32(0)), jnp.int32(1)))
    return b


def power32(keys, n):
    """Vectorized TPU-native PowerHash: keys uint32 [...], n a dynamic scalar.

    The level-descent scheme of :mod:`repro.core.power`, lane-synchronous:
    a scalar shift loop finds the top level ``L = ⌊log2(n−1)⌋`` (integer
    exact — no float log), the top level rejection-resamples until every
    lane draws ``v < n`` (geometric, ≥ ½ success per try, capped at
    ``POWER_TRY_CAP`` with descend as the deterministic fallback), then
    lanes still below ``2^L`` descend one full level per iteration.
    Bit-identical to ``core.power.power32`` (``variant="32"``).
    """
    from repro.core.power import POWER_SALT, POWER_TRY_CAP

    keys = jnp.asarray(keys).astype(_U)
    n = jnp.asarray(n).astype(jnp.int32)

    L = jax.lax.while_loop(lambda L: ((n - 1) >> (L + 1)) > 0,
                           lambda L: L + 1, jnp.int32(0))
    hi_mask = (_U(1) << (L + 1).astype(_U)) - _U(1)
    base = _U(POWER_SALT) + (L.astype(_U) << _U(6))
    v0 = hash2(keys, base) & hi_mask
    t0 = jnp.ones_like(keys, jnp.int32)

    def rcond(state):
        v, t = state
        return jnp.any((v.astype(jnp.int32) >= n) & (t < POWER_TRY_CAP))

    def rbody(state):
        v, t = state
        redo = (v.astype(jnp.int32) >= n) & (t < POWER_TRY_CAP)
        cand = hash2(keys, base + t.astype(_U)) & hi_mask
        return jnp.where(redo, cand, v), jnp.where(redo, t + 1, t)

    v, _ = jax.lax.while_loop(rcond, rbody, (v0, t0))
    vi = v.astype(jnp.int32)
    out = jnp.where((vi < n) & (vi >= (jnp.int32(1) << L)), vi, jnp.int32(-1))

    def dcond(state):
        j, out = state
        return (j >= 0) & jnp.any(out < 0)

    def dbody(state):
        j, out = state
        mask_j = (_U(1) << (j + 1).astype(_U)) - _U(1)
        cand = (hash2(keys, _U(POWER_SALT) + (j.astype(_U) << _U(6)))
                & mask_j).astype(jnp.int32)
        take = (out < 0) & (cand >= (jnp.int32(1) << j))
        return j - 1, jnp.where(take, cand, out)

    _, out = jax.lax.while_loop(dcond, dbody, (L - 1, out))
    return jnp.where(out < 0, 0, out)


def gather1d(table, idx):
    """Row gather of a flat table by a 2-D (or any-D) index block (jnp and
    interpret-mode Pallas only: Mosaic supports 2-D gathers alone)."""
    return jnp.take(table, idx.reshape(-1), axis=0).reshape(idx.shape)


def table_shape2d(pad: int) -> tuple[int, int]:
    """VMEM layout of a flat length-``pad`` table: (rows, 128) lanes when
    128-aligned (every DeviceImage array is), else a thin (pad, 1) column."""
    return (-(-pad // 128), 128) if pad % 128 == 0 else (pad, 1)
