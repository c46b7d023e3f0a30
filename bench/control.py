"""The control of the comparison: the program with a guarantee broken.

    python3 bench/control.py --workload <name> --seconds <s> \
        --control-seeds 1,2,3 [--program-seeds 4,5,...]

The system states no precision, so the control breaks the guarantee that
host and device agree bit for bit: Jump's step ``floor((b + 1) / r)`` is
taken from a float32 multiply by the reciprocal, rounded as the hardware
rounds it, without the integer correction the program makes
(``primitives.floor_rn_quotient``).  That is the step a change for speed
would be tempted to take.  Each run prints one JSON line with the numbers
compared; the control has to come out not correct on every seed.  The
program's own runs (``--program-seeds``) go through the same process, so
set-up is paid once per run and not per process.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def reciprocal_jump_step():
    """Plant the control: Jump's step without its exact rounding."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import primitives

    def rounded_by_hardware(x, den, approx, n):
        q = x.astype(jnp.float32) * jnp.reciprocal(
            den.astype(jnp.float32) * jnp.float32(2.0 ** -24))
        return jnp.where(q >= n.astype(jnp.float32), n,
                         jnp.floor(q).astype(jnp.int32))

    exact = primitives.floor_rn_quotient
    primitives.floor_rn_quotient = rounded_by_hardware
    jax.clear_caches()
    try:
        yield
    finally:
        primitives.floor_rn_quotient = exact
        jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--program-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import load_cell, run_cell

    cell = load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    runs = [("program", int(s)) for s in args.program_seeds.split(",") if s]
    runs += [("control", int(s)) for s in args.control_seeds.split(",")]
    for kind, seed in runs:
        plant = reciprocal_jump_step() if kind == "control" else None
        r = run_cell(cell, seed, args.seconds, False, devices[:cell.chips],
                     time.perf_counter(), plant=plant)
        print(json.dumps({"run": kind, "workload": cell.name, "seed": seed,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "checks": {k: c["value"] for k, c in r["checks"].items()},
                          "metrics": {k: m["value"] for k, m in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
