"""``engine.roofline_pct``: the engine's share of its roofline, in %.

The floor is the bytes any lookup must move, 8 per key (the key in, the
bucket out), over the chip's HBM bandwidth (``bench/peaks.py``); the time
is ``engine.device_ms``, the engine program's device time per run.  A run
of the sharded program looks up its share of the batch on each device."""

from bench.harness import metric_reader
from bench.peaks import lookup_floor_s


def read(ctx):
    ms = metric_reader(ctx.cell, "engine.device_ms")(ctx)
    if not ms:
        return None
    keys_per_run = ctx.cell.traffic["batch_keys"] / ctx.devices
    return 100.0 * lookup_floor_s(keys_per_run, ctx.device_kind) / (ms / 1e3)
