import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we jit the real step function (train_step / prefill / decode)
with production shardings against ShapeDtypeStruct inputs (no allocation),
``.lower().compile()`` it for the 256-chip single-pod mesh and the 512-chip
2-pod mesh, and record:

  * ``memory_analysis()``  — per-device bytes (proves the cell fits 16 GB HBM),
  * ``cost_analysis()``    — per-device FLOPs / bytes-accessed,
  * collective schedule    — parsed from the partitioned HLO, while-loop
                             trip-count weighted (launch/hlo_analysis.py),
  * the three roofline terms (launch/roofline.py).

Results are cached as JSON under benchmarks/results/dryrun/ so the sweep is
resumable; ``--force`` recomputes.

Examples:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import LONG_CONTEXT_ARCHS, ARCHS, SHAPES, get_config
from repro.launch.hlo_analysis import collective_summary
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import roofline_record
from repro.models import LM
from repro.serve.step import (decode_cache_specs, decode_shapes, decode_specs,
                              make_decode_step, make_prefill_step,
                              prefill_shapes, prefill_specs)
from repro.sharding.rules import default_rules
from repro.train.step import (TrainStepConfig, batch_shapes, batch_specs,
                              make_train_step, state_shapes, state_specs)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# train_4k microbatch counts: keep live activations + remat boundaries < HBM
MICROBATCHES = {"llava-next-34b": 16, "qwen2.5-14b": 8, "gemma3-12b": 8,
                "phi3.5-moe-42b-a6.6b": 8, "recurrentgemma-9b": 8}
DEFAULT_MICROBATCHES = 4


def _ns(tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def parse_overrides(items):
    out = {}
    for it in items or []:
        k, _, v = it.partition("=")
        out[k] = tuple(a for a in v.split("+") if a) if v else ()
    return out


def lower_cell(arch: str, shape_name: str, mesh_kind: str, *,
               attn_chunk=512, microbatches=None, remat="full",
               overrides=None, moe_impl="global", cache_dtype="bfloat16",
               verbose=True):
    cfg = get_config(arch)
    shp = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = default_rules(mesh)
    if overrides:
        rules = rules.with_overrides(**overrides)

    model = LM(cfg, attn_chunk=attn_chunk, remat=remat, rules=rules,
               moe_impl=moe_impl, cache_dtype=cache_dtype)
    t0 = time.time()

    if shp.kind == "train":
        nmb = microbatches or MICROBATCHES.get(arch, DEFAULT_MICROBATCHES)
        batch_ways = 1
        for a in rules.rules.get("batch", ()):
            batch_ways *= rules.mesh_axis_sizes.get(a, 1)
        # keep the per-microbatch batch divisible by the batch sharding —
        # otherwise activations silently replicate (measured 4.5× worse)
        nmb = max(1, min(nmb, shp.global_batch // max(batch_ways, 1)))
        step = make_train_step(model, TrainStepConfig(microbatches=nmb), rules=rules)
        in_shapes = (state_shapes(model), batch_shapes(cfg, shp.global_batch, shp.seq_len))
        in_specs = (state_specs(model, rules),
                    batch_specs(cfg, rules, shp.global_batch, shp.seq_len))
        out_specs = (in_specs[0], None)
        jitted = jax.jit(step,
                         in_shardings=_ns(in_specs, mesh),
                         out_shardings=(_ns(out_specs[0], mesh), None),
                         donate_argnums=(0,))
    elif shp.kind == "decode":
        step = make_decode_step(model)
        in_shapes = decode_shapes(model, shp.global_batch, shp.seq_len)
        pspec, _, tokspec, posspec = decode_specs(model, rules, shp.global_batch)
        cspec = decode_cache_specs(model, shp.global_batch, shp.seq_len, rules)
        in_specs = (pspec, cspec, tokspec, posspec)
        jitted = jax.jit(step,
                         in_shardings=_ns(in_specs, mesh),
                         out_shardings=(_ns(cspec, mesh), None),
                         donate_argnums=(1,))
    elif shp.kind == "prefill":
        step = make_prefill_step(model)
        in_shapes = prefill_shapes(model, shp.global_batch, shp.seq_len)
        in_specs = prefill_specs(model, rules, shp.global_batch, shp.seq_len)
        jitted = jax.jit(step, in_shardings=_ns(in_specs, mesh))
    else:
        raise ValueError(shp.kind)

    with mesh:
        lowered = jitted.lower(*in_shapes)
        compiled = lowered.compile()
    compile_s = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    from repro.launch.hlo_analysis import analyze
    hla = analyze(hlo)
    colls = collective_summary(hlo, hla)

    mem = {}
    if ma is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "peak_memory_in_bytes",
                  "alias_size_in_bytes", "generated_code_size_in_bytes"):
            mem[f] = int(getattr(ma, f, 0) or 0)

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": int(mesh.devices.size),
        "compile_seconds": round(compile_s, 1),
        "memory_analysis": mem,
        "cost_analysis": {
            # trip-weighted (scan bodies × trip count) — see hlo_analysis.py
            "flops_per_device": float(hla.flops),
            "bytes_accessed_per_device": float(hla.traffic_bytes),
            # raw XLA statics for cross-checking (undercount scanned models)
            "xla_static_flops": float(ca.get("flops", 0.0)),
            "xla_static_bytes": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": colls,
        "settings": {"attn_chunk": attn_chunk, "remat": remat,
                     "moe_impl": moe_impl, "cache_dtype": cache_dtype,
                     "microbatches": nmb if shp.kind == "train" else None,
                     "overrides": {k: list(v) for k, v in (overrides or {}).items()}},
    }
    record["roofline"] = roofline_record(cfg, shp, record)
    if verbose:
        r = record["roofline"]
        print(f"[dryrun] {arch} × {shape_name} × {mesh_kind}: compile {compile_s:.0f}s  "
              f"peak {mem.get('peak_memory_in_bytes', 0)/2**30:.2f} GiB/dev  "
              f"t_comp {r['t_compute']:.2e}s t_mem {r['t_memory']:.2e}s "
              f"t_coll {r['t_collective_ring']:.2e}s → {r['bottleneck']}", flush=True)
    return record


def cells(mesh_kinds):
    for arch in sorted(ARCHS):
        for shape_name in SHAPES:
            if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                continue
            for mk in mesh_kinds:
                yield arch, shape_name, mk


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--attn-chunk", type=int, default=512)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat", default="full", choices=["full", "names", "none"])
    ap.add_argument("--moe-impl", default="global", choices=["global", "local"])
    ap.add_argument("--cache-dtype", default="bfloat16", choices=["bfloat16", "int8"])
    ap.add_argument("--override", action="append",
                    help="sharding rule override, e.g. --override seq_kv=model")
    ap.add_argument("--out-dir", default=str(RESULTS_DIR))
    args = ap.parse_args()

    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        todo = list(cells(mesh_kinds))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape, mk) for mk in mesh_kinds]

    failures = []
    for arch, shape_name, mk in todo:
        path = out_dir / f"{arch}__{shape_name}__{mk}__{args.variant}.json"
        if path.exists() and not args.force:
            print(f"[dryrun] cached: {path.name}", flush=True)
            continue
        try:
            rec = lower_cell(arch, shape_name, mk,
                             attn_chunk=args.attn_chunk,
                             microbatches=args.microbatches,
                             remat=args.remat,
                             moe_impl=args.moe_impl,
                             cache_dtype=args.cache_dtype,
                             overrides=parse_overrides(args.override))
            path.write_text(json.dumps(rec, indent=1))
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            failures.append((arch, shape_name, mk, repr(e)))
            print(f"[dryrun] FAILED {arch} × {shape_name} × {mk}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} failures:", flush=True)
        for f in failures:
            print("   ", f, flush=True)
        raise SystemExit(1)
    print("[dryrun] all cells OK", flush=True)


if __name__ == "__main__":
    main()
