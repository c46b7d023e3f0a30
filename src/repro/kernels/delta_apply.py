"""Pallas TPU kernel: apply an :class:`~repro.core.protocol.ImageDelta`
scatter to a flat device table (DESIGN.md §3.5).

The control-plane hot path under churn: instead of re-transferring an O(n)
snapshot after every ``remove()``/``add()``, the host ships O(changed-words)
``(index, value)`` pairs and the device edits its resident table.  The
kernel is deliberately out-of-place — output = copy of the input table with
the scatter applied — because the image store double-buffers epochs: the
epoch-N buffer must stay intact (and keep serving lookups) while epoch N+1
is materialized.

Scatter layout: the update indices/values ride in the scalar-prefetch
operand (SMEM), bounded by a dynamic ``count`` so one compiled kernel
serves any delta up to the padded width.  The grid walks the (rows, 128)
table in ``APPLY_BLOCK_ROWS``-row blocks, and each update turns into a
masked vector select over each block — O(count · n/1024 VPU steps), which
for the O(1)-word deltas the algorithms emit is a handful of vector ops
per block.  uint32 tables (the Dx bitmap) are bit-cast through int32 so
the one kernel covers every image array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .primitives import table_shape2d


#: table rows per grid step of the apply kernel: (512, 128) int32 = 256 KiB
#: per block, so input + output double buffers stay far inside VMEM at any
#: table size (a 10⁶-bucket dense image is 7,813 such rows).
APPLY_BLOCK_ROWS = 512


def _apply_kernel(meta_ref, table_ref, out_ref):
    # meta = [count, idx_0..idx_{P-1}, val_0..val_{P-1}] (int32, SMEM)
    count = meta_ref[0]
    pad = (meta_ref.shape[0] - 1) // 2
    tab = table_ref[...]
    rows, cols = tab.shape
    first = pl.program_id(0) * (rows * cols)  # flat index of this block
    flat = (first + lax.broadcasted_iota(jnp.int32, (rows, cols), 0) * cols
            + lax.broadcasted_iota(jnp.int32, (rows, cols), 1))

    def body(j, acc):
        idx = meta_ref[1 + j]
        val = meta_ref[1 + pad + j]
        return jnp.where(flat == idx, val, acc)

    out_ref[...] = lax.fori_loop(0, count, body, tab)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _apply_scatter_i32(meta, table2d, *, interpret: bool):
    """Grid over row blocks of the table: each step copies its block and
    applies the updates that land in it (a block never holds the whole
    table, so VMEM use is independent of the fleet size)."""
    rows, cols = table2d.shape
    block = (min(rows, APPLY_BLOCK_ROWS), cols)
    spec = pl.BlockSpec(block, lambda i, m: (i, 0))
    return pl.pallas_call(
        _apply_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, block[0]),),
            in_specs=[spec],
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct(table2d.shape, jnp.int32),
        interpret=interpret,
    )(meta, table2d)


def _pad_updates(idx, vals, sentinel: int, pad_to: int = 8):
    """Pad (idx, vals) to a power-of-two width ≥ ``pad_to`` so the jitted
    kernels see a handful of shapes, not one per delta size.  Padded slots
    carry ``sentinel`` as their index: -1 for the Pallas kernel (never
    matches a flat position iota), INT32_MAX for the jnp scatter (out of
    bounds for any table, dropped by ``mode="drop"``)."""
    import numpy as np

    k = len(idx)
    width = pad_to
    while width < k:
        width *= 2
    pidx = np.full((width,), sentinel, np.int32)
    pval = np.zeros((width,), np.int32)
    pidx[:k] = idx
    pval[:k] = np.asarray(vals).astype(np.int64).astype(np.int32)
    return pidx, pval, k


@jax.jit
def _scatter_jnp(table, meta):
    # meta = [idx_0..idx_{P-1}, val_0..val_{P-1}] in ONE int32 array: the
    # host→device hop has a fixed per-transfer cost that dwarfs these few
    # words, so the whole delta rides one device_put.  Padded idx slots
    # hold INT32_MAX → dropped.  Compiled once per (table shape, padded
    # width) and reused for every churn event.
    width = meta.shape[0] // 2
    idx, vals = meta[:width], meta[width:]
    return table.at[idx].set(vals.astype(table.dtype), mode="drop")


def compose_updates(update_seq) -> dict:
    """Last-write-wins composition of a sequence of per-array scatter dicts
    (each ``{name: (idx, vals)}``) into ONE such dict.

    The follower-side half of cross-epoch delta batching
    (``launch/replicate.py``): a drained batch of chained frames collapses
    into a single :func:`apply_updates` scatter — one device dispatch per
    drain instead of one per epoch — and positions written by several
    epochs keep only their final value, exactly the dedup rule the leader's
    ``device_delta`` composition applies.  Order within the sequence is the
    epoch order; later writes win.
    """
    import numpy as np

    merged: dict[str, dict[int, int]] = {}
    for updates in update_seq:
        for name, (idx, vals) in updates.items():
            slots = merged.setdefault(name, {})
            for i, v in zip(np.asarray(idx).tolist(),
                            np.asarray(vals).tolist()):
                slots[i] = v
    return {
        name: (np.fromiter(slots.keys(), np.int32, len(slots)),
               np.fromiter(slots.values(), np.int64,
                           len(slots)).astype(np.int32))
        for name, slots in merged.items()
    }


def apply_updates(arrays: dict, updates: dict, *, plane: str = "jnp",
                  interpret: bool | None = None) -> dict:
    """Apply per-array ``{name: (idx, vals)}`` scatters to an image's
    ``arrays`` dict, out of place.

    Untouched arrays (and empty update lists) pass through by reference —
    they stay shared with the previous epoch's image, which is what makes
    double buffering O(changed-words) instead of O(n).  Shared by the
    leader store's delta apply and the follower replica's wire-frame apply,
    so both sides run bit-identical scatter code.
    """
    out = {}
    for name, arr in arrays.items():
        upd = updates.get(name)
        if upd is not None and len(upd[0]):
            out[name] = scatter_update(arr, upd[0], upd[1], plane=plane,
                                       interpret=interpret)
        else:
            out[name] = arr
    return out


def scatter_update(table, idx, vals, *, plane: str = "jnp",
                   interpret: bool | None = None):
    """Out-of-place scatter ``table[idx] = vals`` → new device array.

    ``plane='jnp'`` uses a functional ``.at[].set`` (any backend);
    ``plane='pallas'`` runs the apply-delta kernel (interpret off-TPU;
    ``interpret=None`` follows :func:`~repro.kernels.engine.default_interpret`).
    Either way the input buffer is preserved — the caller keeps it as the
    previous-epoch half of its double buffer.
    """
    table = jnp.asarray(table)
    if plane == "jnp":
        import numpy as np

        pidx, pval, _ = _pad_updates(np.asarray(idx), np.asarray(vals),
                                     sentinel=np.iinfo(np.int32).max)
        # hand the numpy meta straight to jit: ONE dispatch covers the
        # host→device hop and the scatter (the churn hot path).
        return _scatter_jnp(table, np.concatenate([pidx, pval]))
    if plane != "pallas":
        raise ValueError(f"unknown plane {plane!r}")
    import numpy as np

    if table.dtype.itemsize != 4:
        # dtype-narrowed packed tables (int16/int8) cannot bit-cast through
        # the int32 apply kernel (widths differ); their scatter payloads are
        # O(1) words, so the functional path serves them on every backend.
        return scatter_update(table, idx, vals, plane="jnp")
    if interpret is None:
        from .engine import default_interpret
        interpret = default_interpret()
    pidx, pval, k = _pad_updates(np.asarray(idx), np.asarray(vals), sentinel=-1)
    meta = jnp.asarray(np.concatenate([[k], pidx, pval]).astype(np.int32))
    tab_i32 = lax.bitcast_convert_type(table, jnp.int32)
    out = _apply_scatter_i32(meta, tab_i32.reshape(table_shape2d(table.shape[0])),
                             interpret=interpret)
    return lax.bitcast_convert_type(out.reshape(-1), table.dtype)
