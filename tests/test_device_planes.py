"""Device-plane edge cases that are NOT algorithm-generic.

The algorithm × plane bit-identity matrix (host ⇄ jnp ⇄ Pallas, every
registry entry) lives in ``tests/test_conformance.py``; this module keeps
the kernel-specific paths: block-shape independence for the fixed-capacity
kernels, Dx's probe-bound fallback, and plane-name validation.
"""
from __future__ import annotations

import numpy as np
import pytest

from conformance import state
from repro.kernels import ref
from repro.kernels.engine import engine_lookup


@pytest.mark.parametrize("algo", ["anchor", "dx"])
def test_kernel_block_rows_sweep(algo):
    """Block-shape independence for the new kernels (Memento: test_kernels)."""
    h = state(algo, 256, 140, seed=9)
    image = h.device_image()
    keys = np.random.default_rng(8).integers(0, 2**32, size=1500, dtype=np.uint32)
    want = ref.lookup_host(keys, h)
    for block_rows in (1, 4, 16):
        got = np.asarray(engine_lookup(keys, image, block_rows=block_rows))
        np.testing.assert_array_equal(got, want)


def test_dx_fallback_path():
    """A probe-bound overrun must settle on the host's first-working bucket."""
    h = state("dx", 16, 0, seed=0)
    image = h.device_image()
    image.scalars = dict(image.scalars, max_probes=1)  # force overruns
    keys = np.random.default_rng(10).integers(0, 2**32, size=400, dtype=np.uint32)
    out = np.asarray(engine_lookup(keys, image))
    assert set(out.tolist()) <= h.working_set()
    assert (out == image.scalars["fallback"]).any()


def test_device_lookup_rejects_unknown_plane():
    h = state("memento", 16, 0, seed=0)
    with pytest.raises(ValueError):
        engine_lookup(np.zeros(4, np.uint32), h.device_image(), plane="cuda")
