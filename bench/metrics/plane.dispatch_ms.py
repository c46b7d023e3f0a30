"""``plane.dispatch_ms``: host time per batch the sharded plane spends
staging keys and dispatching its program, in ms (exact sums of
``plane.dispatch.us`` over the window)."""


def read(ctx):
    n, total = ctx.hist("plane.dispatch.us")
    return total / n / 1e3 if n else None
