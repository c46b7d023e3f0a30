"""``router.host_ms``: the router's own host time per ``route_batch``, in
ms — the call's time less the store's lookup inside it (id hashing,
polling, telemetry).  Exact sums of the ``router.route_batch.us`` and
``store.lookup.us`` histograms over the window."""


def read(ctx):
    n, total = ctx.hist("router.route_batch.us")
    _, lookup = ctx.hist("store.lookup.us")
    return (total - lookup) / n / 1e3 if n else None
