"""Pallas kernel sweeps vs the pure-jnp oracle (interpret mode on CPU)."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import MementoTables, random_state
from repro.kernels import ref
from repro.kernels.engine import engine_lookup


def _state(n0, removals, seed=0):
    m = random_state(np.random.default_rng(seed), n0, removals, variant="32")
    return m, MementoTables(m)


@pytest.mark.parametrize("n0,removals", [(16, 0), (16, 6), (200, 75), (1024, 500), (4096, 100)])
@pytest.mark.parametrize("nkeys", [1, 100, 1000])
def test_dense_kernel_matches_oracle(n0, removals, nkeys):
    import jax.numpy as jnp

    m, tabs = _state(n0, removals, seed=n0 + nkeys)
    keys = np.random.default_rng(1).integers(0, 2**32, size=nkeys, dtype=np.uint32)
    got = np.asarray(engine_lookup(keys, m.device_image(), plane="pallas"))
    want = np.asarray(ref.memento_lookup_ref(jnp.asarray(keys), jnp.asarray(tabs.repl), m.n))
    np.testing.assert_array_equal(got, want)
    # and against the scalar host plane (end-to-end, three implementations)
    np.testing.assert_array_equal(got, ref.memento_lookup_host(keys, m))


@pytest.mark.parametrize("n0,removals", [(16, 6), (1024, 30), (100000, 200)])
def test_packed_kernel_matches_oracle(n0, removals):
    import jax.numpy as jnp

    from repro.core.packing import pack_image

    m, tabs = _state(n0, removals, seed=7)
    keys = np.random.default_rng(2).integers(0, 2**32, size=777, dtype=np.uint32)
    got = np.asarray(engine_lookup(keys, pack_image(m.device_image()),
                                   plane="pallas"))
    want = np.asarray(ref.memento_lookup_ref(jnp.asarray(keys), jnp.asarray(tabs.repl), m.n))
    np.testing.assert_array_equal(got, want)


def test_packed_slots_are_theta_r():
    """The packed slot table grows with the removed set, not the fleet:
    at least 2·headroom·r slots (load factor ≤ 1/(2·headroom)), at most
    the next power of two above that (and never below 128)."""
    from repro.core.packing import pack_image

    m, _ = _state(100000, 50, seed=3)
    r = len(m.R)
    for headroom in (1, 2):
        slot_b = pack_image(m.device_image(),
                            slot_headroom=headroom).arrays["slot_b"]
        assert 2 * headroom * r <= slot_b.shape[0] <= max(128, 4 * headroom * r)
        assert int((slot_b >= 0).sum()) == r


@pytest.mark.parametrize("dtype", [np.uint32, np.int64, np.uint64])
def test_kernel_key_dtypes(dtype):
    m, tabs = _state(64, 20, seed=4)
    keys = np.random.default_rng(3).integers(0, 2**31, size=130).astype(dtype)
    got = np.asarray(engine_lookup(keys, m.device_image(), plane="pallas"))
    want = ref.memento_lookup_host(keys.astype(np.uint32), m)
    np.testing.assert_array_equal(got, want)


def test_kernel_block_rows_sweep():
    import jax.numpy as jnp

    m, tabs = _state(512, 170, seed=5)
    keys = np.random.default_rng(4).integers(0, 2**32, size=2048, dtype=np.uint32)
    want = np.asarray(ref.memento_lookup_ref(jnp.asarray(keys), jnp.asarray(tabs.repl), m.n))
    for block_rows in (1, 2, 8, 16):
        got = np.asarray(engine_lookup(keys, m.device_image(), plane="pallas",
                                       block_rows=block_rows))
        np.testing.assert_array_equal(got, want)
