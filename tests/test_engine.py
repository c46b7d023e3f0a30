"""Engine specifics beyond the conformance grid (DESIGN.md §6).

The (algorithm × op-mode × plane) bit-identity matrix — plain lookup,
k-replica, fused bounded-replica, bounded assignment, epoch diff — lives
in ``tests/test_conformance.py`` now, derived from the registry.  This
module keeps what is NOT a per-algorithm conformance cell: the engine's
error surfaces, cross-algorithm diffs, and the mesh-sharded serving
plane on top of the engine.
"""
from __future__ import annotations

import numpy as np
import pytest

from conformance import ALGORITHMS, state
from repro.core import DeviceImageStore, make_hash
from repro.kernels import engine, ref

PLANES = ["jnp", "pallas"]

_load_len = engine.bounded_load_len  # the one sizing rule for load words


KEYS = np.random.default_rng(77).integers(0, 2**32, size=700, dtype=np.uint32)


# ---------------------------------------------------------------------------
# Error surfaces and engine-only modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plane", PLANES)
def test_bounded_replica_infeasible_cap_raises(plane):
    """Fewer than k DISTINCT below-cap buckets must raise, like the host
    oracle — never silently return duplicate or over-cap replica sets."""
    h = make_hash("memento", 2, variant="32")
    image = h.device_image()
    load = np.zeros(_load_len(image), np.int32)
    load[1] = 5  # bucket 1 full: only bucket 0 remains below cap
    with pytest.raises(RuntimeError, match="salt budget"):
        engine.engine_lookup(KEYS[:32], image, k=2, load=load, cap=5,
                             plane=plane)
    # the fully-saturated fleet (zero below-cap buckets) raises too
    full = np.full_like(load, 5)
    with pytest.raises(RuntimeError, match="salt budget"):
        engine.engine_lookup(KEYS[:16], image, k=2, load=full, cap=5,
                             plane=plane)


def test_engine_op_validation():
    with pytest.raises(ValueError):
        engine.EngineOp("cuckoo")
    with pytest.raises(ValueError):
        engine.EngineOp("memento", k=0)
    with pytest.raises(ValueError):
        engine.EngineOp("memento", table="compact")
    with pytest.raises(ValueError):
        engine.EngineOp("memento", mode="walk", k=2)
    h = state("memento", 16, 0, seed=0)
    with pytest.raises(ValueError):
        engine.engine_lookup(KEYS[:4], h.device_image(), plane="cuda")
    with pytest.raises(ValueError):
        engine.engine_lookup(KEYS[:4], h.device_image(), load=np.zeros(16))


def test_shim_modules_are_gone():
    """The per-algorithm re-export shims and the ``ops`` wrapper are gone:
    the engine is the only import surface for device lookups."""
    for mod in ("memento_lookup", "anchor_lookup", "dx_lookup",
                "jump_lookup", "replica_lookup", "migrate", "ops"):
        with pytest.raises(ImportError):
            __import__(f"repro.kernels.{mod}")


def test_cross_algo_diff_jnp():
    """Algorithm migrations diff across table layouts on the jnp plane."""
    hm = state("memento", 64, 10, seed=10)
    ha = state("anchor", 64, 10, seed=10)
    d = engine.engine_diff(KEYS[:128], hm.device_image(), ha.device_image(),
                           plane="jnp")
    np.testing.assert_array_equal(d.old, ref.lookup_host(KEYS[:128], hm))
    np.testing.assert_array_equal(d.new, ref.lookup_host(KEYS[:128], ha))
    with pytest.raises(ValueError):
        engine.engine_diff(KEYS[:8], hm.device_image(), ha.device_image(),
                           plane="pallas")


# ---------------------------------------------------------------------------
# Sharded serving plane (this process' devices; multi-device: property test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ALGORITHMS)
def test_sharded_plane_matches_single_device(algo):
    from repro.serve.plane import ShardedLookupPlane

    h = state(algo, 96, 30, seed=11)
    store = DeviceImageStore(h)
    plane = ShardedLookupPlane(store)
    keys = np.random.default_rng(12).integers(0, 2**32, size=4321,
                                              dtype=np.uint32)
    np.testing.assert_array_equal(plane.lookup(keys),
                                  store.lookup(keys, plane="jnp"))
    p2 = ShardedLookupPlane(store, k=2)
    np.testing.assert_array_equal(p2.lookup(keys[:512]),
                                  store.lookup(keys[:512], k=2, plane="jnp"))


def test_sharded_plane_stream_tracks_epochs():
    from repro.serve.plane import ShardedLookupPlane

    h = state("memento", 64, 10, seed=13)
    store = DeviceImageStore(h)
    plane = ShardedLookupPlane(store)
    keys = np.random.default_rng(14).integers(0, 2**32, size=1000,
                                              dtype=np.uint32)

    def batches():
        yield keys
        h.remove(sorted(h.working_set())[0])
        store.sync()  # flips between batches; plane must re-pin
        yield keys

    out0, out1 = list(plane.route_stream(batches()))
    np.testing.assert_array_equal(out1, ref.lookup_host(keys, h))
    assert (out0 != out1).any()


def test_router_route_stream_matches_route_batch():
    from repro.serve.router import SessionRouter

    r = SessionRouter(12)
    ids = [np.arange(i * 64, (i + 1) * 64, dtype=np.uint64) for i in range(3)]
    streamed = list(r.route_stream(iter(ids)))
    for batch, out in zip(ids, streamed):
        np.testing.assert_array_equal(out, r.route_batch(batch))


def test_router_route_stream_honours_mark_failed():
    """Streamed traffic must fail over around a health-marked replica with
    the same rule as route_batch — BEFORE the membership delta lands."""
    from repro.serve.router import SessionRouter

    r = SessionRouter(8, replicas_k=2)
    ids = np.arange(0, 256, dtype=np.uint64)
    primary = r.route_batch(ids)
    victim = int(np.bincount(primary).argmax())
    r.mark_failed(victim)
    want = r.route_batch(ids)
    assert victim not in set(want.tolist())
    (streamed,) = list(r.route_stream([ids]))
    np.testing.assert_array_equal(streamed, want)
    assert r.stats.failovers > 0
    r._failed.clear()
    (clean,) = list(r.route_stream([ids]))
    np.testing.assert_array_equal(clean, primary)


def test_router_route_stream_survives_fleet_collapse():
    """replicas_k > 1 with the fleet collapsed to one survivor: the
    k-clamped (1-D) replica sets must stream without error, matching
    route_batch."""
    from repro.serve.router import SessionRouter

    r = SessionRouter(3, replicas_k=2)
    ids = np.arange(0, 64, dtype=np.uint64)
    r.fail_replica(2)
    r.fail_replica(1)
    r.mark_failed(0)  # every candidate marked → keep the primary
    want = r.route_batch(ids)
    (streamed,) = list(r.route_stream([ids]))
    np.testing.assert_array_equal(streamed, want)


def test_elastic_replica_movement_plan():
    from repro.runtime.elastic import ElasticCluster

    c = ElasticCluster(16, num_shards=64, replica_k=2)
    before = {s: c.replica_hosts(s) for s in range(64)}
    c.fail(5)
    mv = c.replica_movement()
    after = {s: c.replica_hosts(s) for s in range(64)}
    # default identity domains: device plan == host lookup_k churn
    want = {s for s in range(64) if before[s] != after[s]}
    assert set(mv) == want
    for s in mv:
        assert mv[s]["old"] == before[s] and mv[s]["new"] == after[s]
