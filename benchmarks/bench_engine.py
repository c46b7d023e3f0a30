"""Unified-engine benchmark: fused vs multi-launch ops + mesh scale-out.

Two stories (DESIGN.md §6), for the four algorithms across the paper's
§VIII scenario groups (stable / one-shot / incremental, ``variant="32"``):

* **fusion** — the engine's single-program ops against their multi-launch
  decompositions, bit-equality asserted alongside the timing:

    - epoch diff:        ``engine_diff`` (one program, both epoch tables)
      vs two independent lookups + host compare,
    - replica-set diff:  ``engine_diff(k=2)`` vs two k-replica lookups +
      host compare,
    - bounded k-replica: the fused ``engine_lookup(k, load=, cap=)``
      throughput relative to the plain k-replica lookup (the op had no
      single-launch form before the engine),

* **scale-out** — single-device engine throughput vs the mesh-sharded
  :class:`~repro.serve.plane.ShardedLookupPlane` for 10⁵–10⁷-key batches
  (``--full`` reaches 10⁷), with sharded == single-device equality
  asserted.  Run standalone (``python -m benchmarks.bench_engine``) with
  ``JAX_PLATFORMS=cpu``, the module forces
  ``--xla_force_host_platform_device_count=2`` BEFORE jax initializes, so
  the CPU backend exercises a real 2-device mesh; elsewhere, and under
  ``benchmarks.run --engine``, it uses whatever devices exist.

Correctness gates are deterministic and CI-hard (``check_engine_claims``);
timings — including the ≥1.8× two-device target at 10⁶ keys — are
advisory on CPU (interpret-mode Pallas and simulated host devices are not
TPU performance).  ``--out BENCH_engine.json`` writes the artifact CI
uploads and ``benchmarks/report.py`` renders into RESULTS.md.

Beyond timings the benchmark *accounts* (DESIGN.md §8):

* **bytes/key + roofline utilization per op** — the HLO cost model
  (``launch/hlo_analysis.analyze_jit``) over the engine's jnp program,
  divided against the detected backend's roofline
  (``launch/roofline.HARDWARE``; override with ``REPRO_ROOFLINE_HW``),
* **compact images** — the 10⁶-bucket packed-vs-dense table-byte claim
  (``pack_image``; gated ≥ 2× for Memento, with bit-identical lookups),
* **tuning** (``--tune``) — refreshes ``benchmarks/results/
  TUNE_engine.json``, the autotuner cache the engine consults at
  dispatch time.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import ALGORITHM_REGISTRY, ALGORITHMS as ALGOS

SCENARIOS = ("stable", "oneshot", "incremental")


def _remove(h, count, rng):
    for _ in range(count):
        if ALGORITHM_REGISTRY[h.name].lifo_only:
            h.remove(h.size - 1)
        else:
            ws = sorted(h.working_set())
            h.remove(ws[int(rng.integers(len(ws)))])


def _scenario_state(algo, scenario, w, a_over_w, frac, rng):
    from repro.core import make_hash

    h = make_hash(algo, w, capacity=a_over_w * w, variant="32")
    if scenario == "oneshot":
        _remove(h, int(frac * w), rng)
    elif scenario == "incremental":
        # ride out removals one by one (worst-case replacement chains)
        _remove(h, int(frac * w), rng)
        for _ in range(int(0.1 * w)):
            h.add()
            _remove(h, 1, rng)
    return h


from benchmarks.timing import time_fn as _time  # warm-up + block_until_ready


def _lookup_accounting(images, op, keys, n_keys, measured_s):
    """bytes/key + roofline terms for one engine op, from the HLO cost
    model of its jnp program (the canonical algorithmic traffic — the
    Pallas plane runs the same algorithm with hand-placed tiles)."""
    import jax.numpy as jnp

    from repro.kernels.engine import _engine_jnp, _jnp_operands
    from repro.launch.hlo_analysis import analyze_jit
    from repro.launch.roofline import lookup_roofline

    arrays, scalars = _jnp_operands(images)
    a = analyze_jit(_engine_jnp, (jnp.asarray(keys),), arrays, scalars,
                    None, None, static={"op": op})
    return lookup_roofline(a.traffic_bytes, a.flops, n_keys,
                           measured_s=measured_s)


def bench_engine(emit, w=1024, a_over_w=4, key_counts=(100_000, 1_000_000),
                 k_values=(1, 2, 3), algos=ALGOS, scenarios=SCENARIOS,
                 frac=0.5, seed=0):
    """Emit (table, algo, x, metric, value) rows; return the JSON summary."""
    import jax

    from repro.core import DeviceImageStore
    from repro.kernels.engine import engine_diff, engine_lookup
    from repro.serve.plane import ShardedLookupPlane

    from dataclasses import asdict

    from repro.launch.roofline import hardware_spec

    rng = np.random.default_rng(seed)
    devices = len(jax.devices())
    summary: dict = {
        "bench": "engine", "w": w, "key_counts": list(key_counts),
        "k_values": list(k_values),
        "mesh": {"devices": devices, "axes": ["data"]},
        "hardware": asdict(hardware_spec()),
        "results": {},
    }

    for algo in algos:
        for scenario in scenarios:
            h = _scenario_state(algo, scenario, w, a_over_w, frac, rng)
            store = DeviceImageStore(h)
            image = store.image()
            key = f"{algo}_{scenario}"
            entry = summary["results"].setdefault(key, {
                "algo": algo, "scenario": scenario, "working": h.working,
            })

            # -- single-device vs mesh throughput -------------------------
            plane = ShardedLookupPlane(store)
            for n_keys in key_counts:
                keys = rng.integers(0, 2**32, size=n_keys, dtype=np.uint32)
                single = np.asarray(engine_lookup(keys, image, plane="jnp"))
                t_single = _time(lambda: np.asarray(
                    engine_lookup(keys, image, plane="jnp")))
                sharded = plane.lookup(keys)
                t_mesh = _time(lambda: plane.lookup(keys))
                equal = bool(np.array_equal(sharded, single))
                tag = f"{n_keys}"
                emit("engine_throughput", algo, tag,
                     f"{scenario}_single_us_per_key", t_single / n_keys * 1e6)
                emit("engine_throughput", algo, tag,
                     f"{scenario}_mesh{devices}_us_per_key",
                     t_mesh / n_keys * 1e6)
                emit("engine_throughput", algo, tag,
                     f"{scenario}_mesh_speedup", t_single / t_mesh)
                entry[f"single_us_per_key_{n_keys}"] = t_single / n_keys * 1e6
                entry[f"mesh_us_per_key_{n_keys}"] = t_mesh / n_keys * 1e6
                entry[f"mesh_speedup_{n_keys}"] = t_single / t_mesh
                entry["sharded_equal"] = entry.get("sharded_equal", True) and equal

                if n_keys == min(key_counts):
                    from repro.kernels.engine import EngineOp
                    acct = _lookup_accounting(
                        [image], EngineOp(algo=algo), keys, n_keys, t_single)
                    entry["lookup_accounting"] = acct
                    emit("engine_accounting", algo, scenario,
                         "lookup_bytes_per_key", acct["bytes_per_key"])
                    emit("engine_accounting", algo, scenario,
                         "lookup_roofline_utilization",
                         acct["roofline_utilization"])

            # -- fused vs multi-launch ops (smallest key count) -----------
            keys = rng.integers(0, 2**32, size=min(key_counts),
                                dtype=np.uint32)
            nk = len(keys)
            _remove(h, max(1, w // 100), rng)
            store.sync()
            old, new = store.previous_image(), store.image()

            d = engine_diff(keys, old, new, plane="jnp")
            t_fused = _time(lambda: engine_diff(keys, old, new, plane="jnp"))

            def two_launch(k=1):
                o = np.asarray(engine_lookup(keys, old, k=k, plane="jnp"))
                n_ = np.asarray(engine_lookup(keys, new, k=k, plane="jnp"))
                return o, n_, (o != n_) if k == 1 else (o != n_).any(axis=1)

            o2, n2, m2 = two_launch()
            fused_equal = (np.array_equal(d.old, o2)
                           and np.array_equal(d.new, n2)
                           and np.array_equal(d.moved, m2))
            t_two = _time(lambda: two_launch())
            emit("engine_fusion", algo, scenario, "diff_fused_us_per_key",
                 t_fused / nk * 1e6)
            emit("engine_fusion", algo, scenario, "diff_two_launch_us_per_key",
                 t_two / nk * 1e6)
            entry["diff_fused_us_per_key"] = t_fused / nk * 1e6
            entry["diff_two_launch_us_per_key"] = t_two / nk * 1e6

            from repro.kernels.engine import EngineOp
            acct_d = _lookup_accounting(
                [old, new], EngineOp(algo=algo, diff=True), keys, nk, t_fused)
            entry["diff_accounting"] = acct_d
            emit("engine_accounting", algo, scenario, "diff_bytes_per_key",
                 acct_d["bytes_per_key"])
            emit("engine_accounting", algo, scenario,
                 "diff_roofline_utilization", acct_d["roofline_utilization"])

            if max(k_values) > 1:
                kk = max(k for k in k_values if k > 1)
                dk = engine_diff(keys, old, new, k=kk, plane="jnp")
                t_kfused = _time(lambda: engine_diff(keys, old, new, k=kk,
                                                     plane="jnp"))
                ok2, nk2, mk2 = two_launch(kk)
                fused_equal = (fused_equal and np.array_equal(dk.old, ok2)
                               and np.array_equal(dk.new, nk2)
                               and np.array_equal(dk.moved, mk2))
                t_ktwo = _time(lambda: two_launch(kk))
                emit("engine_fusion", algo, scenario,
                     f"replica{kk}_diff_fused_us_per_key", t_kfused / nk * 1e6)
                emit("engine_fusion", algo, scenario,
                     f"replica{kk}_diff_two_launch_us_per_key",
                     t_ktwo / nk * 1e6)
                entry[f"replica{kk}_diff_fused_us_per_key"] = t_kfused / nk * 1e6
                entry[f"replica{kk}_diff_two_launch_us_per_key"] = t_ktwo / nk * 1e6

                # fused bounded k-replica: no pre-engine single-launch form
                from repro.kernels.engine import bounded_load_len
                cap = max(2, math.ceil(1.25 * nk / h.working))
                load = np.zeros(bounded_load_len(new), np.int32)
                full = sorted(h.working_set())[: max(1, h.working // 4)]
                load[full] = cap
                bounded = np.asarray(engine_lookup(
                    keys, new, k=kk, load=load, cap=cap, plane="jnp"))
                entry["bounded_under_cap"] = bool((load[bounded] < cap).all())
                t_bounded = _time(lambda: np.asarray(engine_lookup(
                    keys, new, k=kk, load=load, cap=cap, plane="jnp")))
                t_plain = _time(lambda: np.asarray(engine_lookup(
                    keys, new, k=kk, plane="jnp")))
                emit("engine_fusion", algo, scenario,
                     f"bounded_replica{kk}_us_per_key", t_bounded / nk * 1e6)
                emit("engine_fusion", algo, scenario,
                     f"plain_replica{kk}_us_per_key", t_plain / nk * 1e6)
                entry[f"bounded_replica{kk}_us_per_key"] = t_bounded / nk * 1e6
                entry[f"plain_replica{kk}_us_per_key"] = t_plain / nk * 1e6

            entry["fused_equal"] = fused_equal
    return summary


def bench_compact(emit, n=1_000_000, removals=1024, n_keys=8192, seed=0):
    """The packed-image claim (DESIGN.md §8.2): at 10⁶ buckets, the packed
    Memento table is ≥ 2× smaller than the dense int32 image with
    bit-identical lookups on host, jnp, and Pallas.  Dx is reported against
    the 4·n int32 image it would need WITHOUT its bitmap encoding (its
    dense layout is already packed — the precedent the Memento packing
    follows)."""
    from repro.core import make_hash
    from repro.core.packing import image_table_bytes, pack_image
    from repro.kernels import ref
    from repro.kernels.engine import engine_lookup

    rng = np.random.default_rng(seed)
    out: dict = {}
    for algo in ("memento", "dx"):
        h = make_hash(algo, n, variant="32")
        # distinct random removals: each target is still working when its
        # turn comes, so no O(n·removals) working-set rescans
        for b in rng.choice(n, size=removals, replace=False):
            h.remove(int(b))
        dense = h.device_image()
        packed = pack_image(dense)
        keys = rng.integers(0, 2**32, size=n_keys, dtype=np.uint32)
        host = ref.lookup_host(keys, h)
        planes_equal = True
        for img in (dense, packed):
            for plane in ("jnp", "pallas"):
                got = np.asarray(engine_lookup(keys, img, plane=plane))
                planes_equal &= bool(np.array_equal(got, host))
        db, pb = image_table_bytes(dense), image_table_bytes(packed)
        int32_equiv = 4 * n  # one int32 word per bucket
        ratio = (db if algo == "memento" else int32_equiv) / max(pb, 1)
        t_dense = _time(lambda: np.asarray(
            engine_lookup(keys, dense, plane="jnp")))
        t_packed = _time(lambda: np.asarray(
            engine_lookup(keys, packed, plane="jnp")))
        out[algo] = {
            "n": n, "removals": removals,
            "dense_bytes": int(db), "packed_bytes": int(pb),
            "int32_equivalent_bytes": int(int32_equiv),
            "reduction_ratio": round(ratio, 2),
            "planes_equal": planes_equal,
            "dense_us_per_key": t_dense / n_keys * 1e6,
            "packed_us_per_key": t_packed / n_keys * 1e6,
        }
        emit("engine_compact", algo, f"{n}", "reduction_ratio", ratio)
        emit("engine_compact", algo, f"{n}", "packed_bytes", float(pb))
    return out


def tune_engine(w=1024, n_keys=16_384, seed=0, out_path=None):
    """Refresh the autotuner cache: one cell per (algo × layout) at the
    benchmark's serving shape, saved deterministically (sorted keys) so
    re-tuning on identical hardware is a no-op diff."""
    from repro.core import make_hash
    from repro.core.packing import pack_image
    from repro.kernels import autotune

    rng = np.random.default_rng(seed)
    cache = autotune.TuneCache.load(out_path or autotune.DEFAULT_CACHE_PATH)
    tuned = {}
    for algo in ALGOS:
        h = _scenario_state(algo, "oneshot", w, 4, 0.5, rng)
        images = [h.device_image()]
        images.append(pack_image(h.device_image()))
        for image in images:
            key, cfg = autotune.autotune_lookup(image, n_keys, seed=seed,
                                                cache=cache)
            tuned[key] = {"block_rows": cfg.block_rows, "plane": cfg.plane,
                          "us_per_key": cfg.us_per_key}
            print(f"# tuned {key}: block_rows={cfg.block_rows} "
                  f"plane={cfg.plane} ({cfg.us_per_key} us/key)", flush=True)
    path = cache.save(out_path)
    autotune.set_active_cache(cache)  # dispatch sees the fresh winners
    print(f"# wrote {path} ({len(cache)} entries)")
    return tuned


def check_engine_claims(summary: dict) -> bool:
    """Deterministic acceptance gates (timings stay advisory):

    * sharded lookups equal the single-device engine for every cell,
    * fused diffs (k=1 and k>1) are bit-identical to their two-launch
      decompositions,
    * every fused bounded-replica bucket is below the cap.
    """
    ok = True

    def claim(name, cond):
        nonlocal ok
        print(f"# claim: {name}: {'OK' if cond else 'FAIL'}")
        ok &= bool(cond)

    for key, e in summary["results"].items():
        claim(f"{key}: sharded == single-device", e.get("sharded_equal"))
        claim(f"{key}: fused diff == two-launch diff", e.get("fused_equal"))
        if "bounded_under_cap" in e:
            claim(f"{key}: bounded replicas below cap", e["bounded_under_cap"])
    for algo, c in summary.get("compact", {}).items():
        claim(f"compact[{algo}]: ≥2× table-byte reduction "
              f"({c['reduction_ratio']}×)", c["reduction_ratio"] >= 2)
        claim(f"compact[{algo}]: packed lookups bit-identical on all planes",
              c["planes_equal"])
    devices = summary["mesh"]["devices"]
    for key, e in summary["results"].items():
        for n_keys in summary["key_counts"]:
            sp = e.get(f"mesh_speedup_{n_keys}")
            if sp is not None:
                print(f"# advisory: {key} mesh({devices}) speedup "
                      f"@{n_keys}: {sp:.2f}×")
        acct = e.get("lookup_accounting")
        if acct:
            print(f"# advisory: {key} lookup {acct['bytes_per_key']:.0f} "
                  f"bytes/key, {acct['roofline_utilization']:.1%} of the "
                  f"{acct['hardware']} roofline")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="CI smoke sizes")
    ap.add_argument("--full", action="store_true", help="10⁷-key batches")
    ap.add_argument("--out", default=None, help="write JSON summary here")
    ap.add_argument("--tune", action="store_true",
                    help="refresh the autotuner cache (TUNE_engine.json)")
    ap.add_argument("--no-compact", action="store_true",
                    help="skip the 10⁶-bucket packed-image claim")
    args = ap.parse_args(argv)

    if args.quick:
        kw = dict(w=256, key_counts=(100_000,), k_values=(1, 2),
                  scenarios=("stable", "oneshot"))
    elif args.full:
        kw = dict(w=10_000, key_counts=(100_000, 1_000_000, 10_000_000))
    else:
        kw = dict(w=1024, key_counts=(100_000, 1_000_000))

    rows = []

    def emit(table, algo, x, metric, value):
        rows.append((table, algo, x, metric, value))
        print(f"{table},{algo},{x},{metric},{value:.4f}"
              if isinstance(value, float) else
              f"{table},{algo},{x},{metric},{value}", flush=True)

    print("table,algo,x,metric,value")
    t0 = time.time()
    if args.tune:
        tune_engine()
    summary = bench_engine(emit, **kw)
    if not args.no_compact:
        summary["compact"] = bench_compact(emit)
    ok = check_engine_claims(summary)
    summary["claims_pass"] = bool(ok)
    summary["elapsed_s"] = round(time.time() - t0, 2)
    if args.out:
        Path(args.out).write_text(json.dumps(summary, indent=2) + "\n")
        print(f"# wrote {args.out}")
    print(f"# total {summary['elapsed_s']}s — engine claims: "
          f"{'PASS' if ok else 'MISMATCH'}")
    return 0 if ok else 1


if __name__ == "__main__":
    # On the CPU backend, force a 2-device host platform BEFORE jax
    # initializes so the sharded plane runs over a real mesh.  Only there:
    # on a chip the mesh is the chip's devices, and a simulated one must
    # never be read as a chip result.
    if os.environ.get("JAX_PLATFORMS") == "cpu" and \
            "--xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    sys.exit(main())
