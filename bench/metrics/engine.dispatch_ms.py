"""``engine.dispatch_ms``: host time per engine dispatch, in ms: operand
marshalling, the key upload and the launch (exact sums of the
``engine.dispatch`` span's ``engine.dispatch.us`` histogram over the
window, over every op tag)."""


def read(ctx):
    n, total = ctx.hist("engine.dispatch.us")
    return total / n / 1e3 if n else None
