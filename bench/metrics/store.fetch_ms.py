"""``store.fetch_ms``: time per batch the image store waits for the
engine's answer and copies it to the host, in ms (exact sums of the
``store.fetch`` span's ``store.fetch.us`` histogram over the window)."""


def read(ctx):
    n, total = ctx.hist("store.fetch.us")
    return total / n / 1e3 if n else None
