"""The batch path's spans, device counters and compile counter
(repro.obs, DESIGN.md §11): the span tree of one routed batch on an
injected registry, nothing recorded with telemetry off, the engine's
telemetry on the injected registry, the Memento loop counts against a
plain numpy replay of Alg. 4, the compile counter, the JSONL spans on the
profiler's clock, and the compiled program names the benchmark's
``engine.device_ms`` reader matches."""
from __future__ import annotations

import glob
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import make_hash
from repro.kernels.engine import MEMENTO_SWEEP_HISTOGRAMS, engine_lookup
from repro.obs import MetricRegistry, default_registry, set_default_registry
from repro.serve.router import SessionRouter

ROOT = Path(__file__).resolve().parents[1]


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the benchmark's plain numpy Memento (imports nothing of the program)
REF = _load(ROOT / "bench" / "references" / "memento.py")

BATCH_SPANS = ("router.route_batch", "router.hash", "store.lookup",
               "engine.dispatch", "store.fetch")
STREAM_SPANS = ("router.hash", "plane.stage", "plane.repin", "plane.fetch")


def _ids(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, np.iinfo(np.uint64).max, size=n, dtype=np.uint64)


def _router(registry=None, n: int = 5000, removed: int = 0, seed: int = 3):
    router = SessionRouter(n, algo="memento", registry=registry)
    for b in np.random.default_rng(seed).permutation(n)[:removed].tolist():
        router.ch.remove(b)
    router.image_store().sync()
    return router


# ---------------------------------------------------------------------------
# spans of one batch


def test_route_batch_span_tree_shares_one_batch_id():
    reg = MetricRegistry()
    router = _router(reg)
    router.route_batch(_ids(1024))          # warm: compile outside the check
    before = {s.id for s in reg.tracer.completed()}
    router.route_batch(_ids(1024, seed=1))
    spans = [s for s in reg.tracer.completed() if s.id not in before]
    by_name = {s.name: s for s in spans}
    assert sorted(by_name) == sorted(BATCH_SPANS) and len(spans) == 5
    top = by_name["router.route_batch"]
    assert top.parent == 0
    assert {s.name for s in spans if s.parent == top.id} == \
        {"router.hash", "store.lookup"}
    assert {s.name for s in spans if s.parent == by_name["store.lookup"].id} == \
        {"engine.dispatch", "store.fetch"}
    assert {s.attrs["batch"] for s in spans} == {top.attrs["batch"]}
    assert top.attrs["batch"] > 0
    hists = reg.snapshot()["histograms"]
    for name in BATCH_SPANS:
        counts = [h["count"] for k, h in hists.items()
                  if k.split("{")[0] == f"{name}.us"]
        assert sum(counts) == 2, name
    # each histogram holds its span's own duration
    assert reg.histogram("router.hash.us").max >= by_name["router.hash"].dur_us
    assert "router.batch_keys" not in reg.snapshot()["counters"]


def test_telemetry_off_records_nothing_and_answers_match():
    assert not default_registry().active
    ids = _ids(2048, seed=5)
    plain = _router(None, removed=4000).route_batch(ids)
    traced = _router(MetricRegistry(), removed=4000).route_batch(ids)
    assert plain.dtype == traced.dtype
    np.testing.assert_array_equal(plain, traced)
    assert default_registry().snapshot() == {"counters": {}, "gauges": {},
                                             "histograms": {}}
    assert default_registry().tracer.completed() == []
    ref = REF.Reference(5000)
    for b in np.random.default_rng(3).permutation(5000)[:4000].tolist():
        ref.remove(b)
    np.testing.assert_array_equal(plain, ref.lookup(REF.key_to_u32(ids)))


def test_engine_dispatch_lands_on_the_injected_registry():
    injected, default = MetricRegistry(), MetricRegistry()
    prev = set_default_registry(default)
    try:
        _router(injected).route_batch(_ids(512))
    finally:
        set_default_registry(prev)
    got = {k.split("{")[0] for k in injected.snapshot()["histograms"]}
    assert {"engine.dispatch.us", *MEMENTO_SWEEP_HISTOGRAMS} <= got
    assert injected.counter("engine.lookups").value == 1
    assert not any(k.startswith("engine.")
                   for sec in default.snapshot().values() for k in sec)


# ---------------------------------------------------------------------------
# the Memento loop counters


def _replay_counts(keys, c, n):
    """Alg. 4's two loops over numpy arrays, lane-synchronous like the
    device program: every lane takes each iteration of either loop, which
    runs while any lane has work.  Returns the buckets, the iterations of
    both loops, those of the outer loop, and each lane's iterations with
    work (outer: still on a removed bucket; inner: following its chain)."""
    b = REF.jump32(keys, n).astype(np.int64)
    steps = np.zeros(keys.size, np.int64)
    sweeps = outer = 0
    while (c[b] >= 0).any():
        active = c[b] >= 0
        sweeps += 1
        outer += 1
        steps += active
        wb = np.where(active, c[b], 1)
        d = (REF.hash2(keys, b) % wb.astype(np.uint32)).astype(np.int64)
        while True:
            follow = active & (c[d] >= wb)
            if not follow.any():
                break
            sweeps += 1
            steps += follow
            d = np.where(follow, c[d], d)
        b = np.where(active, d, b)
    return b, sweeps, outer, steps


#: (removed share, buckets, keys a batch): below and past the paper's ~70%
#: knee, at two fleet sizes and two batch sizes
FLEETS = [pytest.param(0.0, 5000, 4096, id="0.0"),
          pytest.param(0.3, 5000, 4096, id="0.3"),
          pytest.param(0.9, 5000, 4096, id="0.9"),
          pytest.param(0.3, 20_000, 1024, id="0.3-20000-1024"),
          pytest.param(0.3, 20_000, 4096, id="0.3-20000-4096"),
          pytest.param(0.9, 20_000, 1024, id="0.9-20000-1024"),
          pytest.param(0.9, 20_000, 4096, id="0.9-20000-4096")]


@pytest.mark.parametrize("removed_share,n,n_keys", FLEETS)
def test_memento_sweep_counts_match_a_numpy_replay(removed_share, n, n_keys):
    """The engine's four loop counts, one value each a batch, equal the
    replay's, and its buckets are the replay's and the host's."""
    h = make_hash("memento", n, variant="32")
    ref = REF.Reference(n)
    removed = np.random.default_rng(11).permutation(n)[:int(removed_share * n)]
    for b in removed.tolist():
        h.remove(b)
        ref.remove(b)
    keys = np.random.default_rng(12).integers(0, 2**32, n_keys, dtype=np.uint32)
    want_b, want_sweeps, want_outer, steps = _replay_counts(keys, ref._c, ref.n)
    reg = MetricRegistry()
    out = np.asarray(engine_lookup(keys, h.device_image(), plane="jnp",
                                   registry=reg))
    reg.flush_device()
    hists = {name.rsplit(".", 1)[1]: reg.histogram(name)
             for name in MEMENTO_SWEEP_HISTOGRAMS}
    assert {k: v.count for k, v in hists.items()} == dict.fromkeys(hists, 1)
    assert {k: v.sum for k, v in hists.items()} == {
        "sweeps": want_sweeps, "lane_sweeps": int(steps.sum()),
        "outer_sweeps": want_outer, "longest_lane": int(steps.max())}
    np.testing.assert_array_equal(out, want_b)
    np.testing.assert_array_equal(out, [h.lookup(int(k)) for k in keys])
    if removed_share:
        # each outer iteration waits for the slowest chain of any lane
        assert want_sweeps > steps.max() >= want_outer >= 1
    if removed_share == 0.9:
        assert want_sweeps > 10 and 0 < steps.sum() < want_sweeps * n_keys


def test_counts_are_queued_until_the_result_is_fetched():
    h = make_hash("memento", 1000, variant="32")
    reg = MetricRegistry()
    keys = np.arange(256, dtype=np.uint32)
    for _ in range(3):
        engine_lookup(keys, h.device_image(), plane="jnp", registry=reg)
    assert reg.histogram("engine.memento.sweeps").count == 0
    assert reg.snapshot()["histograms"]["engine.memento.sweeps"]["count"] == 3


def test_compile_counter_counts_new_shapes_only():
    reg = MetricRegistry()
    router = _router(reg)
    hist = reg.histogram("device.compile.us")
    assert "device.compile.us" in reg.snapshot()["histograms"]
    n_keys = 3371                     # a shape no other test routes
    before = hist.count
    router.route_batch(_ids(n_keys))
    compiled = hist.count - before
    assert compiled >= 1 and hist.sum > 0
    router.route_batch(_ids(n_keys, seed=2))
    assert hist.count - before == compiled
    # a registry nobody injected is not fed
    assert MetricRegistry().snapshot()["histograms"] == {}


# ---------------------------------------------------------------------------
# the profiler's clock


def _profile(log_dir: Path, work) -> tuple[dict, int]:
    """Run ``work`` under the profiler inside a ``bench.`` span; returns
    the host events ``bench/tracing.extract`` keeps and the session's
    start on the profiler's clock."""
    import sys
    sys.path.insert(0, str(ROOT))
    try:
        from bench import tracing
    finally:
        sys.path.remove(str(ROOT))
    tracing.start(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            work()
    finally:
        tracing.stop()
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(files[0])
    start = next(int(v) for p in data.planes for k, v in p.stats
                 if k == "profile_start_time")
    return tracing.extract(str(log_dir)), start


def test_jsonl_spans_land_on_their_profiler_events(tmp_path):
    reg = MetricRegistry()
    router = _router(reg, removed=100)
    router.route_batch(_ids(1024))                    # compile before tracing
    batches = [_ids(1024, seed=s) for s in range(4)]
    list(router.route_stream(iter(batches[:2])))

    def work():
        router.route_batch(_ids(1024, seed=9))
        stream = router.route_stream(iter(batches))
        next(stream)
        router.fail_replica(int(sorted(router.replicas)[0]))  # re-pin
        list(stream)

    skip = len(reg.sink.events("span"))
    events, profile_start = _profile(tmp_path, work)
    host = {}
    for s, _d, name in sorted(events["host"]):
        host.setdefault(name, []).append(profile_start + s)
    for name in set(BATCH_SPANS) | set(STREAM_SPANS):
        assert name in host, name
    clock = reg.sink.events("clock")[0]
    assert clock == {"kind": "clock", **reg.tracer.clock}
    spans = reg.sink.events("span")[skip:]
    assert {e["name"] for e in spans} >= set(BATCH_SPANS) | set(STREAM_SPANS)
    seen: dict[str, int] = {}
    for ev in sorted(spans, key=lambda e: e["start_us"]):
        i = seen[ev["name"]] = seen.get(ev["name"], -1) + 1
        at = clock["profiler_ns"] + ev["start_us"] * 1e3
        assert abs(host[ev["name"]][i] - at) < 200e3, ev
    assert all(len(host[name]) == n + 1 for name, n in seen.items())


# ---------------------------------------------------------------------------
# the compiled program names the benchmark's engine.device_ms reader matches


def test_engine_program_names_match_the_device_time_reader():
    import jax.numpy as jnp

    from repro.kernels.engine import EngineOp, _engine_jnp, _jnp_operands
    from repro.launch.mesh import make_lookup_mesh
    from repro.serve.plane import sharded_lookup_program

    programs = _load(ROOT / "bench" / "metrics" / "engine.device_ms.py").ENGINE_PROGRAMS
    img = make_hash("memento", 1000, variant="32").device_image()
    arrays, scalars = _jnp_operands([img])
    keys = jnp.zeros(256, jnp.uint32)
    one = _engine_jnp.lower((keys,), arrays, scalars, None, None,
                            op=EngineOp("memento")).compile().as_text()
    mesh = make_lookup_mesh()
    fn = sharded_lookup_program(EngineOp("memento"), mesh, ("data",),
                                plane="jnp", block_rows=8, interpret=True)
    shard = fn.lower(jnp.zeros(128 * mesh.size, jnp.int32), dict(img.arrays),
                     tuple(jnp.int32(s) for s in scalars)).compile().as_text()
    names = [t.split("HloModule ", 1)[1].split(",")[0].split()[0]
             for t in (one, shard)]
    assert names == list(programs) == ["jit__engine_jnp", "jit_per_shard"]
