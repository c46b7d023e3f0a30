"""Batched consistent-hash lookups — pure-jnp data plane.

Bit-identical to the numpy/scalar host plane (``variant="32"`` states):
the shared 32-bit arithmetic lives in :mod:`repro.kernels.primitives` and
is consumed both here and by the Pallas kernels, so all three planes
(host / jnp / Pallas) agree exactly.  These functions are the oracle for
the kernels (``kernels/ref.py`` re-exports them) and the CPU fallback used
by the data/serving substrates for bulk routing.

One lookup per algorithm (Memento, Anchor, Dx, Jump) over its flat
:class:`~repro.core.protocol.DeviceImage`; :func:`lookup_image` dispatches.
All loops are lane-synchronous masked ``lax.while_loop``s: a whole key
block iterates until every lane settles.  Memento's loops carry the table
word their condition tests, so each sweep is one ``repl`` gather over the
block (a condition and its body are separate computations and would
otherwise both gather it).  Expected sweep counts: Memento
E[τ], E[σ] ≤ ln(n/w) (paper Props. VII.1-3); Anchor ≈ ln(a/w); Dx the
geometric O(a/w) probe count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.primitives import (fmix32, hash2, jump32, power32,
                                      step_u24 as _step_u24)

_U = jnp.uint32

# Back-compat alias: earlier revisions exposed ``hash2_32`` here.
hash2_32 = hash2


def memento_lookup(keys, repl, n):
    """Paper Alg. 4, vectorized: keys uint32 [...], repl int32 [cap], n int.

    Returns int32 bucket ids in [0, n) that are working buckets.  One
    ``repl`` gather over the block starts the loops and one pays for each
    sweep (:func:`_memento_loops`).
    """
    keys = jnp.asarray(keys).astype(_U)
    return _memento_loops(keys, repl, n, (), lambda acc, work, outer: ())[0]


def memento_lookup_counted(keys, repl, n):
    """:func:`memento_lookup` that also counts its loops' work.

    Returns ``(buckets, sweeps, lane_sweeps, outer_sweeps, longest_lane)``:
    ``sweeps`` (int32) is the number of iterations of the outer and inner
    loops together, each one ``repl`` gather over the whole block;
    ``lane_sweeps`` (uint32, wraps at 2³²) sums, over the lanes, the
    iterations in which the lane did work (outer: still on a removed
    bucket; inner: still following the chain).  Their ratio over the
    block size is the share of each sweep spent on unsettled lanes.
    ``outer_sweeps`` (int32) counts the outer loop's iterations alone,
    each of which starts a fresh inner loop; ``longest_lane`` (uint32) is
    the most iterations any one lane did work in, the largest of the
    per-lane counts ``lane_sweeps`` sums.
    """
    keys = jnp.asarray(keys).astype(_U)
    b, (sweeps, outer, lanes) = _memento_loops(
        keys, repl, n, (jnp.int32(0), jnp.int32(0), jnp.zeros_like(keys)),
        lambda acc, work, outer: (acc[0] + 1, acc[1] + int(outer),
                                  acc[2] + work.astype(_U)))
    return b, sweeps, jnp.sum(lanes, dtype=_U), outer, jnp.max(lanes, initial=0)


def _memento_loops(keys, repl, n, acc, tally):
    """Alg. 4's two lane-synchronous loops; ``acc`` rides both loops'
    carries and ``tally(acc, work, outer)`` folds in each iteration's mask
    of lanes with work, ``outer`` true for the outer loop's (``()`` and a
    no-op carry nothing extra).

    Each carry holds the ``repl`` word of the index beside it (outer
    ``c = repl[b]``, inner ``u = repl[d]``), so the conditions read only
    carried values and each sweep is one ``repl`` gather: one before the
    outer loop, one at each inner loop's entry, one per inner iteration.
    The inner loop's last word is the next outer test's ``repl[b]``."""
    b = jump32(keys, n)

    def outer_cond(state):
        _, c, _ = state
        return jnp.any(c >= 0)

    def outer_body(state):
        b, c, acc = state
        active = c >= 0
        wb = jnp.where(active, c, 1)  # |W_b| (Prop. V.3); dummy 1 when settled
        h = hash2(keys, b)
        d = (h % wb.astype(_U)).astype(jnp.int32)

        def inner_cond(state):
            _, u, _ = state
            return jnp.any(active & (u >= 0) & (u >= wb))

        def inner_body(state):
            d, u, acc = state
            follow = active & (u >= 0) & (u >= wb)  # only while u ≥ w_b (balance)
            d = jnp.where(follow, u, d)
            return d, repl[d], tally(acc, follow, False)

        d, u, acc = jax.lax.while_loop(inner_cond, inner_body,
                                       (d, repl[d], tally(acc, active, True)))
        return jnp.where(active, d, b), jnp.where(active, u, c), acc

    b, _, acc = jax.lax.while_loop(outer_cond, outer_body, (b, repl[b], acc))
    return b, acc


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
    """Dispatch a batched jnp lookup over any :class:`DeviceImage` (eager)."""
    from repro.core.protocol import image_scalar_vec

    keys = jnp.asarray(keys, dtype=jnp.uint32)
    arrays = {k: jnp.asarray(v) for k, v in image.arrays.items()}
    return lookup_dispatch(image.algo, keys, arrays, image_scalar_vec(image))


def lookup_image_jit(keys, image):
    """Jitted :func:`lookup_image` — now a shim over the unified engine's
    jnp configuration (kept for one release alongside the kernel shims):
    compiles once per (algo, shapes) and is reused across epochs, since
    the epoch store's stable 128-padded capacities make every churn event
    shape-preserving."""
    from repro.kernels.engine import engine_lookup

    return engine_lookup(keys, image, plane="jnp")


def memento_lookup_hosted(keys, memento_tables):
    """Convenience: run the data plane against a host `MementoTables`."""
    repl = jnp.asarray(memento_tables.repl)
    return memento_lookup(jnp.asarray(keys, dtype=jnp.uint32), repl, memento_tables.n)
