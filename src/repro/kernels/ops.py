"""Jitted public wrappers around the unified lookup engine.

:func:`device_lookup` is the algorithm-generic entry point: it takes any
:class:`~repro.core.protocol.DeviceImage` (Memento, Anchor, Dx, Jump) and
runs the matching :class:`~repro.kernels.engine.EngineOp` configuration,
so routers / placements / benchmarks are algorithm-pluggable end to end.
Every configuration — plain lookup, k-replica, bounded, epoch diff —
compiles to exactly one Pallas launch (DESIGN.md §6).

Execution planes:

  * ``plane='pallas'`` — the engine's Pallas launch (default).  On non-TPU
    backends it runs in interpret mode (the validation path); on TPU it
    compiles via Mosaic where :func:`~repro.kernels.engine.mosaic_compiles`
    admits the configuration and refuses otherwise.
  * ``plane='jnp'``    — the engine's pure-jnp program (no Pallas; any
    backend; also the per-shard body of the mesh-sharded
    :class:`~repro.serve.plane.ShardedLookupPlane`).
  * ``plane='auto'``   — the autotuner's winner for this (op, batch,
    table-size) cell (``kernels/autotune.py``), falling back to Pallas on
    TPU where Mosaic compiles the op and jnp otherwise.

Table layouts (``table``):

  * ``'dense'``   — Θ(n) int32 VMEM image (default; n ≤ ~3M fits VMEM),
  * ``'compact'`` — Θ(r) open-addressing VMEM image (Memento only;
    beyond-paper, for huge b-arrays with few removals),
  * ``'packed'``  — auto-selected for packed DeviceImages (bitmap + slots
    for Memento, narrowed dtypes for Anchor; ``repro.core.packing``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import jax_lookup as _jnp
from . import engine as _engine


def device_lookup(keys, image, *, plane: str = "pallas", table: str = "dense",
                  k: int = 1, load=None, cap: int | None = None,
                  interpret: bool | None = None, block_rows: int | None = None):
    """Batched lookup over any DeviceImage: keys [K] → working bucket ids
    [K] (or [K, k] replica sets for ``k > 1``; with ``load``/``cap`` every
    returned bucket is additionally below the load cap — the fused
    bounded-replica configuration, still one launch)."""
    keys = jnp.asarray(keys, dtype=jnp.uint32)
    packed = getattr(image, "packed", False)
    if plane == "auto":
        from . import autotune
        op = _engine.EngineOp(algo=image.algo, k=k,
                              bounded=load is not None,
                              table="packed" if packed else table)
        plane = autotune.resolve_plane(op, int(keys.shape[0]), int(image.n))
    if plane == "jnp" and k == 1 and load is None and not packed:
        return _jnp.lookup_image(keys, image)
    if plane not in ("jnp", "pallas"):
        raise ValueError(f"unknown plane {plane!r}")
    if table not in ("dense", "packed") and image.algo != "memento":
        raise ValueError(f"unknown table kind {table!r} for {image.algo!r}")
    return _engine.engine_lookup(keys, image, k=k, load=load, cap=cap,
                                 plane=plane, table=table,
                                 interpret=interpret,
                                 block_rows=block_rows)
