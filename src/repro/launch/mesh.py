"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (data=16, model=16) = 256 chips (v5e pod);
multi-pod adds a leading ``pod`` axis: (2, 16, 16) = 512 chips.  The dry-run
launcher sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before
any jax import so these meshes build on the CPU container.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale distribution tests (requires enough devices)."""
    return _mesh(shape, axes)


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int) -> None:
    """Join the multi-process mesh for cross-process delta replication
    (DESIGN.md §9.3): process 0 owns membership, followers receive the
    broadcast delta frames of :mod:`repro.launch.replicate`.

    On the CPU backend, cross-process collectives need the gloo
    implementation — the default CPU client rejects multi-process
    computations — so it is selected *before* ``jax.distributed``
    initializes the backend (a no-op on TPU, where ICI collectives are
    native).
    """
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def make_lookup_mesh(num_devices: int | None = None, axis: str = "data"):
    """1-D serving mesh for the sharded lookup plane (DESIGN.md §6): key
    batches shard over ``axis`` across every available device (or the
    first ``num_devices``), images replicate.  On the CPU container the
    device count comes from ``--xla_force_host_platform_device_count``."""
    n = num_devices or len(jax.devices())
    return _mesh((n,), (axis,))
