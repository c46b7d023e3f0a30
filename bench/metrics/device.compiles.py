"""``device.compiles``: XLA compiles inside the window (the count of the
program's ``device.compile.us`` histogram, fed by ``jax.monitoring``).
Should be 0: the warm-up compiles every shape.  Nothing to read where the
program keeps no such histogram."""


def read(ctx):
    if "device.compile.us" not in ctx.obs:
        return None
    return float(ctx.hist("device.compile.us")[0])
