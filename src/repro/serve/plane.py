"""ShardedLookupPlane — mesh-sharded serving for million-key batches.

The multi-device face of the lookup engine (DESIGN.md §6): one
``shard_map`` over the axes of a :mod:`repro.launch.mesh` mesh fans a key
batch across every device — each shard runs the engine's per-shard body
(the jnp dispatch off-TPU, the one-launch Pallas configuration on TPU)
against a **per-device replicated** copy of the
:class:`~repro.core.protocol.DeviceImage`.  The image rides a
:class:`~repro.core.DeviceImageStore` wherever the caller has one, so
membership churn reaches every device as the store's O(changed-words)
epoch deltas and the plane just re-pins the flipped front image
(``_ensure``); plain images and raw ConsistentHash states work too.

Throughput mechanics:

  * keys are padded to ``devices × 128`` lanes and sharded over the mesh
    axes; the image arrays and dynamic scalars are device_put once per
    epoch with a replicated sharding (no per-call broadcast),
  * the staged key buffer is **donated** to the jitted sharded program, so
    steady-state streaming keeps exactly two key buffers and two result
    buffers alive (double buffering),
  * :meth:`route_stream` overlaps host-side result materialization of
    batch *i* with device compute of batch *i+1* (dispatch is async).

Correctness: a sharded lookup is bit-identical to the single-device
engine for ANY mesh shape — the per-shard body is elementwise over keys
(tests/test_engine.py, including the forced multi-device subprocess
check).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.packing import image_table_names
from repro.core.protocol import image_scalar_vec
from repro.obs.metrics import default_registry as _default_obs


def _is_store(source) -> bool:
    return hasattr(source, "image") and hasattr(source, "sync")


class ShardedLookupPlane:
    """Fan engine lookups over a device mesh with per-device images.

    ``source`` is a :class:`~repro.core.DeviceImageStore` (preferred: its
    epoch deltas keep the replicated image fresh), a raw
    :class:`~repro.core.protocol.DeviceImage`, or any ConsistentHash host
    state (snapshot on epoch change).  ``mesh`` defaults to a 1-D
    ``("data",)`` mesh over every device
    (:func:`repro.launch.mesh.make_lookup_mesh`); any mesh works — keys
    shard over the product of ``axes`` (default: all mesh axes).
    """

    def __init__(self, source, *, mesh=None, axes: tuple[str, ...] | None = None,
                 k: int = 1, plane: str = "jnp", interpret: bool | None = None,
                 block_rows: int | None = None, sync_mode: str = "block",
                 registry=None):
        from repro.kernels.engine import default_interpret

        if plane not in ("jnp", "pallas", "auto"):
            raise ValueError(f"unknown plane {plane!r}")
        if k < 1:
            raise ValueError("k must be ≥ 1")
        if sync_mode not in ("block", "overlap"):
            raise ValueError(f"unknown sync_mode {sync_mode!r}")
        self.sync_mode = sync_mode
        if mesh is None:
            from repro.launch.mesh import make_lookup_mesh
            mesh = make_lookup_mesh()
        self.mesh = mesh
        self.axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        self.k = k
        self.plane = plane
        self._interpret = (default_interpret() if interpret is None
                           else interpret)
        self._block_rows = block_rows
        self._source = source
        self._registry = registry  # None → follow the process default
        self._image = None       # host-side image the device copy mirrors
        self._dev = None         # (arrays dict, scalars tuple) replicated
        self._rep_cache: dict = {}  # name → (source array, replicated copy)
        self._fns: dict = {}     # (algo, shape sig, padded) → jitted program

    def _obs(self):
        """The live telemetry registry (injected, else process default)."""
        return self._registry or _default_obs()

    # -- mesh geometry -------------------------------------------------------
    @property
    def num_shards(self) -> int:  # obs-exempt: mesh geometry
        n = 1
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        for a in self.axes:
            n *= sizes[a]
        return n

    @property
    def lanes(self) -> int:  # obs-exempt: mesh geometry
        """Key-count granularity: every shard gets 128-aligned rows."""
        return self.num_shards * 128

    # -- image replication ---------------------------------------------------
    def _poll_source(self) -> None:
        """``sync_mode='overlap'``: land the store's pending async epoch iff
        its device result is ready (non-blocking), so the flip + re-pin
        pipeline between ``route_stream`` batches instead of stalling one."""
        if self.sync_mode == "overlap" and _is_store(self._source):
            poll = getattr(self._source, "poll", None)
            if poll is not None:
                poll()

    def _current_image(self):
        if _is_store(self._source):
            return self._source.image()
        if hasattr(self._source, "device_image"):
            src = self._source
            if self._image is not None and \
                    getattr(src, "epoch", None) == self._image.epoch:
                return self._image
            return src.device_image()
        return self._source  # a plain DeviceImage

    def _ensure(self):
        """Re-pin the replicated per-device image iff the epoch flipped.

        Arrays the store's out-of-place delta apply did NOT touch are the
        same objects across epochs, so their replicated copies are reused
        — per-flip fan-out cost is O(changed arrays), and the compiled
        sharded programs survive flips (they are keyed by shape, and every
        operand is an argument, not a constant)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        img = self._current_image()
        if self._dev is not None and img is self._image:
            return
        reg = self._obs()
        reg.counter("plane.repins").inc()
        with reg.timed("plane.repin", epoch=img.epoch):
            rep = NamedSharding(self.mesh, P())
            names = image_table_names(img)
            arrays = {}
            for n in names:
                src = img.arrays[n]
                cached = self._rep_cache.get(n)
                if cached is None or cached[0] is not src:
                    self._rep_cache[n] = (src, jax.device_put(
                        jnp.asarray(src), rep))
                arrays[n] = self._rep_cache[n][1]
            scalars = tuple(jax.device_put(jnp.asarray(s, jnp.int32), rep)
                            for s in image_scalar_vec(img))
        self._image = img
        self._dev = (arrays, scalars)

    # -- the sharded program -------------------------------------------------
    def _sharded_fn(self, padded: int):
        """One jitted shard_map program per (algo, table shapes, padded
        key count) — epoch flips at stable shapes reuse the compiled
        program (the store pads capacities exactly so this holds)."""
        arrays, _ = self._dev
        packed = getattr(self._image, "packed", False)
        key = (self._image.algo, packed,
               tuple(sorted((n, a.shape) for n, a in arrays.items())),
               padded)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        from repro.kernels import autotune
        from repro.kernels.engine import EngineOp

        op = EngineOp(algo=self._image.algo, k=self.k,
                      table="packed" if packed else "dense")
        # tuned parameters resolve once, at program-build time, against the
        # per-shard batch this program will always see (padded is part of
        # the fn cache key, so the resolution is as static as the jit key).
        shard_keys = padded // self.num_shards
        table_n = int(self._image.n)
        plane = self.plane
        if plane == "auto":
            plane = autotune.resolve_plane(op, shard_keys, table_n)
        block_rows = (self._block_rows if self._block_rows is not None
                      else autotune.resolve_block_rows(op, shard_keys, table_n))
        fn = sharded_lookup_program(op, self.mesh, self.axes, plane=plane,
                                    block_rows=block_rows,
                                    interpret=self._interpret)
        self._fns[key] = fn
        return fn

    def _stage(self, keys) -> tuple:
        """Pad + device_put a key batch with the sharded layout."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        keys = np.asarray(keys, dtype=np.uint32)
        n = len(keys)
        padded = max(self.lanes, -(-n // self.lanes) * self.lanes)
        buf = np.zeros(padded, np.int32)  # donated: int32 so results alias
        buf[:n] = keys.view(np.int32)
        key_spec = P(self.axes if len(self.axes) > 1 else self.axes[0])
        dev = jax.device_put(jnp.asarray(buf),
                             NamedSharding(self.mesh, key_spec))
        return dev, n, padded

    # -- public data plane ---------------------------------------------------
    def lookup(self, keys) -> np.ndarray:
        """Sharded batched lookup: keys [K] → np int32 [K] (k=1) or [K, k]."""
        reg = self._obs()
        batch = reg.next_batch()
        t0 = time.perf_counter_ns() if reg.active else 0
        out, n = self.lookup_async(keys, batch=batch)
        res = self._fetch(reg, out, n, batch)
        if reg.active:
            self._record_batch(reg, n, out.shape[-1], t0)
        return res

    def lookup_async(self, keys, *, batch: int = 0):
        """Dispatch one batch without waiting: returns the still-sharded
        device result (int32 ``[padded]``, or ``[k, padded]``, split over
        the mesh) and the number of real keys at its front.  Picks up any
        epoch flip first, like every batch.  ``batch`` is the id its
        ``plane.stage`` span carries."""
        # obs-exempt: lookup/route_stream record the batch
        self._poll_source()
        self._ensure()
        with self._obs().timed("plane.stage", batch=batch):
            dev, n, padded = self._stage(keys)
        arrays, scalars = self._dev
        return self._sharded_fn(padded)(dev, arrays, scalars), n

    def route_stream(self, batches):
        """Stream key batches through the plane with double buffering.

        Yields one np result per input batch, in order.  The donated key
        buffers and the one-batch pipeline keep host staging of batch
        *i+1* overlapped with device compute of batch *i*.  Spans (each
        with its ``.us`` histogram): ``plane.repin`` on an epoch flip,
        ``plane.stage`` and ``plane.fetch`` (the wait for a batch's answer
        and its copy to the host), the last two with the batch's id.
        """
        reg = self._obs()
        pending = None  # (device out, n, batch id)
        for keys in batches:
            batch = reg.next_batch()
            t0 = time.perf_counter_ns() if reg.active else 0
            out, n = self.lookup_async(keys, batch=batch)
            if reg.active:  # dispatch latency — materialization overlaps
                self._record_batch(reg, n, out.shape[-1], t0)
            if pending is not None:
                yield self._fetch(reg, *pending)
            pending = (out, n, batch)
        if pending is not None:
            yield self._fetch(reg, *pending)

    def _fetch(self, reg, out, n: int, batch: int) -> np.ndarray:
        with reg.timed("plane.fetch", batch=batch):
            return self._finish(out, n)

    def _record_batch(self, reg, n: int, padded: int, t0_ns: int) -> None:
        """Per-batch plane telemetry: batch/key counters, the per-shard
        batch-size distribution, and the host-side dispatch latency."""
        reg.counter("plane.batches").inc()
        reg.counter("plane.keys").inc(n)
        reg.histogram("plane.shard_keys").observe(padded // self.num_shards)
        reg.histogram("plane.dispatch.us").observe(
            (time.perf_counter_ns() - t0_ns) / 1e3)

    def _finish(self, out, n) -> np.ndarray:
        out = np.asarray(out)
        return out[:n] if self.k == 1 else out[:, :n].T


def sharded_lookup_program(op, mesh, axes: tuple[str, ...], *, plane: str,
                           block_rows: int, interpret: bool):
    """The jitted ``shard_map`` program of one :class:`EngineOp`: int32 key
    buffer sharded over ``axes`` (donated for k=1), image tables as a
    name → array dict and layout scalars as a tuple, both replicated.
    ``plane`` is resolved ("jnp" or "pallas")."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels.engine import (_engine_pallas, _mode_outputs,
                                      _pad_rows, _tables2d)

    names = op.table_names
    shard_dim = axes if len(axes) > 1 else axes[0]
    key_spec = P(shard_dim)

    def per_shard(keys, arrays, scalars):
        # keys travel as an int32 buffer so the k=1 result (int32, same
        # shape) can alias the donated input; bitcast restores uint32.
        keys = jax.lax.bitcast_convert_type(keys, jnp.uint32)
        if plane == "jnp":
            outs = _mode_outputs(op, (keys,), [arrays[n] for n in names],
                                 scalars, None, None)
        else:  # one Pallas launch per shard, tables in VMEM
            keys2d, nk = _pad_rows(keys)
            tabs = tuple(_tables2d([arrays[n] for n in names]))
            scal = (jnp.stack(scalars) if scalars
                    else jnp.zeros((0,), jnp.int32))
            raw = _engine_pallas(scal, (keys2d,), tabs, op=op,
                                 block_rows=block_rows, interpret=interpret)
            outs = [o.reshape(-1)[:nk] for o in raw]
        return outs[0] if op.k == 1 else jnp.stack(outs)  # [K'] | [k, K']

    # Pallas kernel bodies carry no varying-axis types, so only the jnp
    # body is checked (its loop carries start from the keys, DESIGN.md §6)
    f = jax.shard_map(per_shard, mesh=mesh, in_specs=(key_spec, P(), P()),
                      out_specs=key_spec if op.k == 1 else P(None, shard_dim),
                      check_vma=plane == "jnp")
    # k=1: the int32 result aliases the donated int32 key buffer —
    # steady-state streaming keeps two buffers alive, not 2×batches.
    return jax.jit(f, donate_argnums=(0,) if op.k == 1 else ())
