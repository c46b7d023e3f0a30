"""``store.sync_ms``: time per image-store sync (delta apply and flip), in
ms (exact sums of ``store.sync.us`` over the window)."""


def read(ctx):
    n, total = ctx.hist("store.sync.us")
    return total / n / 1e3 if n else None
