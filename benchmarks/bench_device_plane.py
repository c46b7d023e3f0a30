"""Framework benchmark: batched device-plane lookup vs the host plane.

Compares, at several cluster sizes / removal ratios, µs-per-key of:
  * host scalar Python (the control plane — paper methodology),
  * the unified engine's jnp program (jit; CPU backend here, TPU in
    production),
  * the unified engine's Pallas launch in interpret mode (correctness
    path; off-TPU only — Mosaic cannot compile Memento's table gather).

Both device rows are the SAME ``EngineOp`` configuration (DESIGN.md §6) —
only the plane differs.  Interpret-mode timings are NOT TPU performance —
the derived column to watch is µs/key of the jnp path (XLA-compiled
vectorized lookup) vs the scalar host plane: the data plane amortization
that makes bulk routing viable.
"""
from __future__ import annotations

import time

import numpy as np


def bench_device_plane(emit, sizes=((1024, 0), (1024, 300), (65536, 2000)),
                       n_keys=16384):
    import jax.numpy as jnp
    from repro.core import random_state
    from repro.kernels.engine import default_interpret, engine_lookup

    keys = np.random.default_rng(0).integers(0, 2**32, size=n_keys, dtype=np.uint32)
    jkeys = jnp.asarray(keys)

    for n0, removals in sizes:
        m = random_state(np.random.default_rng(1), n0, removals, variant="32")
        image = m.device_image()
        tag = f"n{n0}_r{removals}"

        t0 = time.perf_counter()
        for k in keys[:2000]:
            m.lookup(int(k))
        emit("device_plane", "host_scalar", tag, "us_per_key",
             (time.perf_counter() - t0) / 2000 * 1e6)

        out = engine_lookup(jkeys, image, plane="jnp")  # compile+warm
        out.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            engine_lookup(jkeys, image, plane="jnp").block_until_ready()
        emit("device_plane", "jnp_batched", tag, "us_per_key",
             (time.perf_counter() - t0) / (5 * n_keys) * 1e6)

        if not default_interpret():
            continue  # the interpreter is an off-TPU correctness path only
        out2 = engine_lookup(jkeys, image, plane="pallas")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
        t0 = time.perf_counter()
        engine_lookup(jkeys, image, plane="pallas").block_until_ready()
        emit("device_plane", "pallas_interpret", tag, "us_per_key",
             (time.perf_counter() - t0) / n_keys * 1e6)
