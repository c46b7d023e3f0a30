"""Paper-reproduction benchmarks: lookup time + memory for every
registered algorithm (Memento / Anchor / Dx / Jump / Power) across the
paper's scenarios (§VIII).

Scenarios (one function per paper figure group):

  * stable            — Figs. 17/18: no removals, sizes 10…10⁶
  * one-shot removals — Figs. 19-22: 90 % of nodes removed, LIFO (best) and
                        random (worst)
  * incremental       — Figs. 23-26: growing removal fraction
  * sensitivity       — Figs. 27-32: Anchor/Dx vs the a/w over-provisioning
                        ratio ∈ {5,10,20,50,100}
  * quality           — §II metrics: balance, minimal disruption, monotonicity

Anchor and Dx are initialized with a = 10·w (the paper's compromise).
Default sizes are CPU-budget scaled; ``--full`` switches to paper scale
(10⁶ nodes).  Timings are wall-clock over pre-generated uint64 keys.

``bench_device_scenarios`` additionally times the *device* data plane
(batched jnp + Pallas lookups over each algorithm's DeviceImage) across
the stable / one-shot / incremental scenarios — the comparison §VIII never
ran on hardware.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (ALGORITHM_REGISTRY, ALGORITHMS, JumpHash,
                        MementoHash, PowerHash, make_hash)

A_OVER_W = 10

#: algorithms whose only legal removal is the highest bucket id
_LIFO = frozenset(n for n in ALGORITHMS if ALGORITHM_REGISTRY[n].lifo_only)


def _mk(algo: str, w: int, a_over_w: int = A_OVER_W, variant: str = "64"):
    """Every registered algorithm through the one ConsistentHash factory."""
    return make_hash(algo, w, capacity=a_over_w * w, variant=variant)


def _time_lookup(h, keys) -> float:
    """µs per lookup."""
    lookup = h.lookup
    t0 = time.perf_counter()
    for k in keys:
        lookup(k)
    return (time.perf_counter() - t0) / len(keys) * 1e6


def _keys(n, seed=0):
    return [int(k) for k in np.random.default_rng(seed).integers(0, 2**63, size=n)]


def _remove_random(h, count, seed=1):
    rng = np.random.default_rng(seed)
    ws = sorted(h.working_set())  # maintained incrementally: Θ(a) scan once
    for _ in range(count):
        i = int(rng.integers(len(ws)))
        h.remove(ws[i])
        ws.pop(i)


def _remove_lifo(h, count):
    for _ in range(count):
        if isinstance(h, (MementoHash, JumpHash, PowerHash)):
            h.remove(h.n - 1)
        else:
            h.remove(max(h.working_set()))


ALGOS = ALGORITHMS


def bench_stable(sizes, n_keys, emit):
    keys = _keys(n_keys)
    for w in sizes:
        for algo in ALGOS:
            h = _mk(algo, w)
            us = _time_lookup(h, keys)
            emit("stable_lookup", algo, w, "us_per_lookup", us)
            emit("stable_memory", algo, w, "bytes", h.memory_bytes())


def bench_oneshot(sizes, n_keys, emit, frac=0.9):
    keys = _keys(n_keys)
    for w in sizes:
        removals = int(frac * w)
        for case, remover in (("best", _remove_lifo), ("worst", _remove_random)):
            for algo in ALGOS:
                h = _mk(algo, w)
                if algo in _LIFO:
                    _remove_lifo(h, removals)  # Jump/Power support LIFO only
                else:
                    remover(h, removals)
                us = _time_lookup(h, keys)
                emit(f"oneshot_{case}_lookup", algo, w, "us_per_lookup", us)
                emit(f"oneshot_{case}_memory", algo, w, "bytes", h.memory_bytes())


def bench_incremental(w0, fractions, n_keys, emit):
    keys = _keys(n_keys)
    for case in ("best", "worst"):
        for algo in ALGOS:
            h = _mk(algo, w0)
            removed = 0
            for frac in fractions:
                target = int(frac * w0)
                step = target - removed
                if algo in _LIFO or case == "best":
                    _remove_lifo(h, step)
                else:
                    _remove_random(h, step, seed=int(frac * 100))
                removed = target
                us = _time_lookup(h, keys)
                emit(f"incremental_{case}_lookup", algo, frac, "us_per_lookup", us)
                emit(f"incremental_{case}_memory", algo, frac, "bytes", h.memory_bytes())


def bench_sensitivity(w, ratios, n_keys, emit):
    keys = _keys(n_keys)
    for scenario, frac in (("stable", 0.0), ("removed20", 0.2), ("removed65", 0.65)):
        # Memento baseline (no a/w dependence)
        m = MementoHash(w)
        if frac:
            _remove_random(m, int(frac * w))
        emit(f"sensitivity_{scenario}_lookup", "memento", 0, "us_per_lookup",
             _time_lookup(m, keys))
        emit(f"sensitivity_{scenario}_memory", "memento", 0, "bytes",
             m.memory_bytes())
        for ratio in ratios:
            for algo in ("anchor", "dx"):
                h = _mk(algo, w, a_over_w=ratio)
                if frac:
                    _remove_random(h, int(frac * w))
                emit(f"sensitivity_{scenario}_lookup", algo, ratio,
                     "us_per_lookup", _time_lookup(h, keys))
                emit(f"sensitivity_{scenario}_memory", algo, ratio, "bytes",
                     h.memory_bytes())


def bench_quality(w, n_keys, emit, removals_frac=0.3):
    """§II metrics: balance / minimal disruption / monotonicity, all algos."""
    keys = _keys(n_keys)
    for algo in ALGOS:
        h = _mk(algo, w)
        if algo not in _LIFO:
            _remove_random(h, int(removals_frac * w))
        else:
            _remove_lifo(h, int(removals_frac * w))
        live = len(h.working_set())
        counts: dict[int, int] = {}
        before = {}
        for k in keys:
            b = h.lookup(k)
            before[k] = b
            counts[b] = counts.get(b, 0) + 1
        arr = np.asarray(list(counts.values()) + [0] * (live - len(counts)))
        expected = len(keys) / live
        emit("quality_balance", algo, w, "peak_to_mean", float(arr.max() / expected))
        emit("quality_balance", algo, w, "cv", float(arr.std() / expected))
        # CV × √E ≈ 1 for an ideal uniform assignment (multinomial noise)
        emit("quality_balance", algo, w, "cv_normalized",
             float(arr.std() / expected * np.sqrt(expected)))

        # minimal disruption: remove one more bucket
        victim = sorted(h.working_set())[-1] if algo in _LIFO else sorted(h.working_set())[len(h.working_set()) // 2]
        h.remove(victim)
        moved_bad = sum(1 for k in keys
                        if before[k] != victim and h.lookup(k) != before[k])
        emit("quality_min_disruption", algo, w, "bad_moves", moved_bad)

        # monotonicity: add it back
        b = h.add()
        moved_bad = sum(1 for k in keys if h.lookup(k) not in (before[k], b))
        emit("quality_monotonicity", algo, w, "bad_moves", moved_bad)


def bench_resize(w, n_ops, emit):
    """Table I resize/init columns: add/remove cost."""
    for algo in ALGOS:
        h = _mk(algo, w)
        rng = np.random.default_rng(0)
        ws = sorted(h.working_set())
        victims = [ws[int(rng.integers(len(ws)))] for _ in range(n_ops)]
        t0 = time.perf_counter()
        for v in victims:
            if algo in _LIFO:
                h.remove(h.n - 1)
            else:
                h.remove(v)
            h.add()
        us = (time.perf_counter() - t0) / (2 * n_ops) * 1e6
        emit("resize", algo, w, "us_per_op", us)

        t0 = time.perf_counter()
        _mk(algo, w)
        emit("init", algo, w, "us", (time.perf_counter() - t0) * 1e6)


# ---------------------------------------------------------------------------
# Device plane: bulk-lookup timings for every registry algorithm (§VIII scenarios)
# ---------------------------------------------------------------------------

def bench_device_scenarios(emit, w=1024, a_over_w=4, n_keys=8192,
                           oneshot_frac=0.5, inc_fractions=(0.2, 0.5),
                           pallas_keys=2048):
    """Bulk device-plane lookups (jnp jit + Pallas) per algorithm × scenario.

    Scenarios mirror the paper's §VIII groups on `variant="32"` states whose
    host lookups are bit-identical to the device planes:

      * ``stable``       — no removals,
      * ``oneshot``      — `oneshot_frac` of nodes removed at random
                           (LIFO for Jump, which supports nothing else),
      * ``incremental``  — growing removal fraction, re-timed per step.

    On CPU the Pallas column runs in interpret mode (correctness path, NOT
    TPU performance) over a smaller key batch; the jnp column is the
    XLA-compiled number to watch off-TPU.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.jax_lookup import lookup_image
    from repro.kernels import ops
    from repro.kernels.engine import (EngineOp, default_interpret,
                                      mosaic_compiles)

    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 2**32, size=n_keys, dtype=np.uint32))
    pkeys = jnp.asarray(np.asarray(keys)[:pallas_keys])

    def _time_planes(h, scenario, x):
        image = h.device_image()
        jnp_lookup = jax.jit(lambda k: lookup_image(k, image))
        out = jnp_lookup(keys)
        out.block_until_ready()  # compile+warm
        t0 = time.perf_counter()
        for _ in range(5):
            jnp_lookup(keys).block_until_ready()
        emit(f"device_{scenario}_lookup", h.name, x, "jnp_us_per_key",
             (time.perf_counter() - t0) / (5 * n_keys) * 1e6)

        emit(f"device_{scenario}_memory", h.name, x, "bytes", h.memory_bytes())
        if not (default_interpret() or mosaic_compiles(EngineOp(image.algo))):
            return  # Mosaic cannot compile this table-backed body (§6)
        pout = ops.device_lookup(pkeys, image)  # interpret on CPU, Mosaic on TPU
        pout.block_until_ready()
        np.testing.assert_array_equal(np.asarray(out)[:pallas_keys], np.asarray(pout))
        t0 = time.perf_counter()
        ops.device_lookup(pkeys, image).block_until_ready()
        emit(f"device_{scenario}_lookup", h.name, x, "pallas_us_per_key",
             (time.perf_counter() - t0) / pallas_keys * 1e6)

    for algo in ALGOS:
        # stable
        h = _mk(algo, w, a_over_w=a_over_w, variant="32")
        _time_planes(h, "stable", w)

        # one-shot removals
        h = _mk(algo, w, a_over_w=a_over_w, variant="32")
        removals = int(oneshot_frac * w)
        if algo in _LIFO:
            _remove_lifo(h, removals)
        else:
            _remove_random(h, removals)
        _time_planes(h, "oneshot", w)

        # incremental removals
        h = _mk(algo, w, a_over_w=a_over_w, variant="32")
        removed = 0
        for frac in inc_fractions:
            step = int(frac * w) - removed
            if algo in _LIFO:
                _remove_lifo(h, step)
            else:
                _remove_random(h, step, seed=int(frac * 100))
            removed += step
            _time_planes(h, "incremental", frac)
