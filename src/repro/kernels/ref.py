"""Pure-jnp + scalar-host oracles for the Pallas lookup kernels.

The jnp lookup bodies live in :mod:`repro.core.jax_lookup` (every
device plane runs them, through ``kernels/engine.algo_body``); re-exported here so kernel
tests read naturally as ``kernel(...) == ref(...)``.  The scalar host
oracle works for ANY ConsistentHash implementation (Memento, Anchor, Dx,
Jump) — end-to-end cross-plane checks run host vs jnp vs Pallas.
"""
from __future__ import annotations

import numpy as np

from repro.core.jax_lookup import anchor_lookup as anchor_lookup_ref  # noqa: F401
from repro.core.jax_lookup import dx_lookup as dx_lookup_ref  # noqa: F401
from repro.core.jax_lookup import jump32 as jump32_ref  # noqa: F401
from repro.core.jax_lookup import lookup_image as lookup_image_ref  # noqa: F401
from repro.core.jax_lookup import memento_lookup as memento_lookup_ref  # noqa: F401


def lookup_host(keys: np.ndarray, h) -> np.ndarray:
    """Scalar host-plane oracle: per-key python ``lookup`` of any algorithm."""
    return np.asarray([h.lookup(int(k)) for k in np.asarray(keys)], dtype=np.int32)


def memento_lookup_host(keys: np.ndarray, memento) -> np.ndarray:
    """Scalar host-plane oracle (paper Alg. 4 via the Θ(r) dict)."""
    return lookup_host(keys, memento)
