"""Backend-dependent rules: hardware peaks, the compile cache's place, and
the plane a TPU is handed.  Each test steers the backend by monkeypatching
what the code observes; nothing here needs a chip."""
from __future__ import annotations

import itertools
from types import SimpleNamespace

import pytest

from conformance import ALGORITHMS
from repro.kernels import autotune
from repro.kernels.engine import EngineOp, mosaic_compiles
from repro.launch import compile_cache, roofline


def _fake_device(monkeypatch, platform: str, kind: str) -> None:
    import jax

    dev = SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
    monkeypatch.delenv(roofline.HARDWARE_ENV, raising=False)


def test_detect_hardware_maps_v5e_by_device_kind(monkeypatch):
    _fake_device(monkeypatch, "tpu", "TPU v5 lite")
    assert roofline.detect_hardware() == "tpu-v5e"
    spec = roofline.hardware_spec()
    # published v5e peaks: 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI
    assert (spec.peak_flops, spec.mem_bw, spec.link_bw) == \
        (197e12, 819e9, 200e9)


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v7x"), ("tpu", "TPU v4"), ("gpu", "NVIDIA H100 80GB HBM3"),
])
def test_detect_hardware_refuses_unknown_kinds(monkeypatch, platform, kind):
    _fake_device(monkeypatch, platform, kind)
    with pytest.raises(ValueError, match="no roofline peaks"):
        roofline.detect_hardware()


def test_detect_hardware_cpu_and_override(monkeypatch):
    _fake_device(monkeypatch, "cpu", "cpu")
    assert roofline.detect_hardware() == "cpu-host"
    monkeypatch.setenv(roofline.HARDWARE_ENV, "tpu-v5e")
    assert roofline.detect_hardware() == "tpu-v5e"
    monkeypatch.setenv(roofline.HARDWARE_ENV, "tpu-v9")
    with pytest.raises(ValueError):
        roofline.detect_hardware()


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.compile_cache_dir() == tmp_path


def test_compile_cache_fallback_is_one_fixed_path(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert first.name == ".jax_cache"
    assert (first.parent / "chip_smoke.py").exists()  # inside the checkout


def _all_ops():
    for algo, k, bounded, diff in itertools.product(
            ALGORITHMS, (1, 2), (False, True), (False, True)):
        yield EngineOp(algo, k=k, bounded=bounded, diff=diff)
    for algo in ALGORITHMS:
        yield EngineOp(algo, mode="walk")


@pytest.mark.parametrize("cached", [None, "pallas", "jnp"])
def test_resolve_plane_on_tpu_never_picks_a_refused_plane(cached):
    """Cache hit or miss, ``plane="auto"`` on a TPU resolves to a plane the
    engine compiles for that op: Pallas only where Mosaic compiles it."""
    cache = autotune.TuneCache({})
    ops = list(_all_ops())
    if cached is not None:
        for op in ops:
            cache.put(autotune.grid_key(op, 1 << 20, 10**6, backend="tpu"),
                      autotune.TunedConfig(plane=cached))
    autotune.set_active_cache(cache)
    try:
        for op in ops:
            plane = autotune.resolve_plane(op, 1 << 20, 10**6, backend="tpu")
            assert plane in ("jnp", "pallas"), op
            if plane == "pallas":
                assert mosaic_compiles(op), op
            if cached is None:  # a miss prefers the compiled kernel
                assert plane == ("pallas" if mosaic_compiles(op) else "jnp")
    finally:
        autotune.set_active_cache(None)


def test_autotune_on_tpu_measures_only_planes_that_compile(monkeypatch):
    """Tuning a table-backed op where Pallas is compiled (a TPU) times the
    jnp program alone instead of dispatching a refused kernel."""
    from repro.core import make_hash
    from repro.kernels import engine

    monkeypatch.setattr(engine, "default_interpret", lambda: False)
    image = make_hash("memento", 64, variant="32").device_image()
    cache = autotune.TuneCache({})
    key, cfg = autotune.autotune_lookup(image, 512, repeats=1, cache=cache,
                                        backend="tpu")
    assert cfg.plane == "jnp" and cache.get(key) == cfg


def test_pallas_on_tpu_refuses_gather_ops_before_mosaic():
    """Dispatching a table-backed op to compiled Pallas names the op."""
    import jax.numpy as jnp

    from repro.core import make_hash
    from repro.kernels.engine import engine_lookup

    image = make_hash("memento", 16, variant="32").device_image()
    with pytest.raises(ValueError, match="cannot compile memento"):
        engine_lookup(jnp.arange(8, dtype=jnp.uint32), image, plane="pallas",
                      interpret=False)


def test_one_interpret_rule(monkeypatch):
    """The store, the follower and the sharded plane all take interpret
    mode from the engine's one rule."""
    import jax

    from repro.core import DeviceImageStore, make_hash
    from repro.launch.mesh import make_lookup_mesh
    from repro.launch.replicate import FollowerImageStore
    from repro.serve.plane import ShardedLookupPlane

    h = make_hash("jump", 8, variant="32")
    mesh = make_lookup_mesh(1)
    for backend, want in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert DeviceImageStore(h)._interpret is want
        assert FollowerImageStore()._interpret is want
        assert ShardedLookupPlane(h.device_image(),
                                  mesh=mesh)._interpret is want
