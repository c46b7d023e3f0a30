"""Batched consistent-hash lookups — the device lookup bodies.

Bit-identical to the numpy/scalar host plane (``variant="32"`` states):
the shared 32-bit arithmetic lives in :mod:`repro.kernels.primitives`.
These are the ONLY lookup bodies for the table-backed algorithms
(Memento, Anchor, Dx; Jump and Power are the shared primitives): the jnp
programs, the Pallas kernel and the sharded plane all run them, through
:func:`repro.kernels.engine.algo_body`, which picks the body and, for
Memento, the table reader of each table layout.  ``kernels/ref.py``
re-exports them as the kernels' oracle.

One lookup per algorithm over its flat
:class:`~repro.core.protocol.DeviceImage`; :func:`lookup_image` dispatches.
All loops are lane-synchronous masked ``lax.while_loop``s: a whole key
block iterates until every lane settles.  Memento runs Alg. 4's two loops
as one, each lane in its own phase, so a block takes as many sweeps as
its slowest key takes steps; the loop carries the table word its
condition tests, so each sweep is one table read over the block (a
condition and its body are separate computations and would otherwise
both read it).  Expected sweep counts: Memento
E[τ], E[σ] ≤ ln(n/w) (paper Props. VII.1-3); Anchor ≈ ln(a/w); Dx the
geometric O(a/w) probe count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.primitives import fmix32, hash2, jump32, power32

_U = jnp.uint32


def memento_lookup(keys, repl, n):
    """Paper Alg. 4, vectorized: keys uint32 [...], repl int32 [cap], n int.

    Returns int32 bucket ids in [0, n) that are working buckets.  One
    ``repl`` gather over the block starts the loop and one pays for each
    sweep (:func:`_memento_loop`).
    """
    keys = jnp.asarray(keys).astype(_U)
    return _memento_loop(keys, lambda i: repl[i], n, (),
                          lambda acc, work, rehash: ())[0]


def memento_lookup_counted(keys, repl, n):
    """:func:`memento_lookup` that also counts its loop's work.

    Returns ``(buckets, sweeps, lane_sweeps, outer_sweeps, longest_lane)``:
    ``sweeps`` (int32) is the number of iterations of the loop, each one
    ``repl`` gather over the whole block; ``lane_sweeps`` (uint32, wraps
    at 2³²) sums, over the lanes, the iterations in which the lane did
    work (a re-hash or a chain follow).  Their ratio over the block size
    is the share of each sweep spent on unsettled lanes.
    ``outer_sweeps`` (int32) is the most re-hash steps of any one lane
    (the most outer iterations of Alg. 4 any key takes); ``longest_lane``
    (uint32) is the most iterations any one lane did work in, the largest
    of the per-lane counts ``lane_sweeps`` sums.  The loop runs until
    its slowest lane settles, so ``sweeps == longest_lane``.
    """
    keys = jnp.asarray(keys).astype(_U)
    zero = jnp.zeros_like(keys)
    b, (sweeps, lanes, rehashes) = _memento_loop(
        keys, lambda i: repl[i], n, (jnp.int32(0), zero, zero),
        lambda acc, work, rehash: (acc[0] + 1, acc[1] + work.astype(_U),
                                   acc[2] + rehash.astype(_U)))
    return (b, sweeps, jnp.sum(lanes, dtype=_U),
            jnp.max(rehashes, initial=0).astype(jnp.int32),
            jnp.max(lanes, initial=0))


def _memento_loop(keys, read, n, acc, tally):
    """Alg. 4 as one lane-synchronous loop over a table reader:
    ``read(idx) -> int32`` is the ``repl`` word of each index (−1 =
    working), a dense gather or a packed-image probe
    (:func:`repro.kernels.engine.packed_reader`).  ``acc`` rides the
    carry and ``tally(acc, work, rehash)`` folds in each iteration's
    masks of lanes with work and of lanes that re-hashed (``()`` and a
    no-op carry nothing extra).

    Each lane keeps its own phase of Alg. 4's two loops, so the block
    runs as many sweeps as its slowest key takes steps.  The carry is
    ``(d, w, u)`` with ``u = read(d)``: the candidate, the working-set
    size ``w_b`` of the last re-hash, and the candidate's word.  A lane
    with ``u < 0`` has settled on ``d``.  Otherwise it takes one step:
    it follows its chain (``d = u``) while ``u ≥ w`` (the inner loop),
    else it re-hashes from ``b = d`` into ``w = u`` buckets (the outer
    loop's next iteration, whose test ``read(b)`` is the word in hand).
    ``w`` starts above any word, so a lane's first step is a re-hash.
    The condition reads only the carried word, and each sweep is one
    read: one before the loop and one per iteration."""
    d = jump32(keys, n)
    w = jnp.full_like(d, jnp.iinfo(jnp.int32).max)

    def cond(state):
        _, _, u, _ = state
        return jnp.any(u >= 0)

    def body(state):
        d, w, u, acc = state
        work = u >= 0
        follow = work & (u >= w)  # only while u ≥ w_b (balance)
        rehash = work & ~follow
        w = jnp.where(rehash, u, w)  # |W_b| (Prop. V.3), ≥ 1
        h = (hash2(keys, d) % w.astype(_U)).astype(jnp.int32)
        d = jnp.where(follow, u, jnp.where(rehash, h, d))
        return d, w, read(d), tally(acc, work, rehash)

    d, _, _, acc = jax.lax.while_loop(cond, body, (d, w, read(d), acc))
    return d, acc


def anchor_lookup(keys, A, K, a):
    """AnchorHash lookup over the A/K image: keys uint32 [...], a dynamic int.

    Mirrors the host loop exactly: start at ``fmix32(key) % a``; while the
    bucket is removed, re-hash into its wrap set and follow K successors
    while the candidate was removed at-or-after it.
    """
    keys = jnp.asarray(keys).astype(_U)
    au = jnp.asarray(a).astype(_U)
    b = (fmix32(keys) % au).astype(jnp.int32)

    def outer_cond(b):
        return jnp.any(A[b] > 0)

    def outer_body(b):
        Ab = A[b]
        active = Ab > 0
        denom = jnp.where(active, Ab, 1).astype(_U)
        h = (hash2(keys, b) % denom).astype(jnp.int32)

        def inner_cond(h):
            return jnp.any(active & (A[h] >= Ab))

        def inner_body(h):
            follow = active & (A[h] >= Ab)  # h removed at-or-after b ⇒ wrap
            return jnp.where(follow, K[h], h)

        h = jax.lax.while_loop(inner_cond, inner_body, h)
        return jnp.where(active, h, b)

    return jax.lax.while_loop(outer_cond, outer_body, b)


def dx_lookup(keys, words, a, max_probes, fallback):
    """DxHash lookup over the packed active bitmap: first working bucket in
    the pseudo-random probe stream ``hash(key, i) % a``, i < max_probes;
    unsettled lanes take the precomputed first-working ``fallback``."""
    keys = jnp.asarray(keys).astype(_U)
    au = jnp.asarray(a).astype(_U)
    b0 = jnp.zeros_like(keys, jnp.int32)
    found0 = jnp.zeros_like(keys, jnp.bool_)

    def cond(state):
        i, _, found = state
        return (i < max_probes) & jnp.any(~found)

    def body(state):
        i, b, found = state
        cand = (hash2(keys, i) % au).astype(jnp.int32)
        w = words[cand >> 5]
        bit = (w >> (cand & 31).astype(_U)) & _U(1)
        hit = ~found & (bit == _U(1))
        return i + jnp.int32(1), jnp.where(hit, cand, b), found | hit

    _, b, found = jax.lax.while_loop(cond, body, (jnp.int32(0), b0, found0))
    return jnp.where(found, b, jnp.asarray(fallback, jnp.int32))


def lookup_dispatch(algo, keys, arrays, scalars):
    """Batched lookup from (arrays, layout-ordered scalars) — every operand
    may be traced, so one jitted program serves ANY epoch of a given shape
    (``n`` and friends travel as dynamic scalars, not compile-time
    constants)."""
    if algo == "memento":
        return memento_lookup(keys, arrays["repl"], scalars[0])
    if algo == "anchor":
        return anchor_lookup(keys, arrays["A"], arrays["K"], scalars[0])
    if algo == "dx":
        return dx_lookup(keys, arrays["words"], scalars[0], scalars[1],
                         scalars[2])
    if algo == "jump":
        return jump32(keys, scalars[0])
    if algo == "power":
        return power32(keys, scalars[0])
    raise ValueError(f"unknown device image algo {algo!r}")


def lookup_image(keys, image):
    """Dispatch a batched jnp lookup over any :class:`DeviceImage` (eager):
    the oracle tests hold the engine's planes to."""
    from repro.core.protocol import image_scalar_vec

    keys = jnp.asarray(keys, dtype=jnp.uint32)
    arrays = {k: jnp.asarray(v) for k, v in image.arrays.items()}
    return lookup_dispatch(image.algo, keys, arrays, image_scalar_vec(image))
