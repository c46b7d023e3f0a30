"""``router.hash_ms``: host time per batch the router spends hashing the
session ids to 32-bit keys (``np_key_to_u32``), in ms (exact sums of the
``router.hash`` span's ``router.hash.us`` histogram over the window)."""


def read(ctx):
    n, total = ctx.hist("router.hash.us")
    return total / n / 1e3 if n else None
