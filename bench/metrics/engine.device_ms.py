"""``engine.device_ms``: device time of one run of the engine's lookup
program, in ms, from the profiler trace: the single-device program
``jit__engine_jnp`` or the sharded ``jit_per_shard``, averaged over runs
and devices."""

ENGINE_PROGRAMS = ("jit__engine_jnp", "jit_per_shard")


def read(ctx):
    if not ctx.trace:
        return None
    runs = sum(ctx.trace["program_runs"].get(p, 0) for p in ENGINE_PROGRAMS)
    secs = sum(ctx.trace["program_s"].get(p, 0.0) for p in ENGINE_PROGRAMS)
    return secs / runs * 1e3 if runs else None
