"""``device.idle_pct``: the share of the traced window in which no
operation ran on the device, in %, averaged over devices (busy time is
the union of the trace's device operation intervals)."""


def read(ctx):
    busy = ctx.trace["busy_s"] if ctx.trace else {}
    if not busy or ctx.trace["window_s"] <= 0:
        return None
    mean = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean / ctx.trace["window_s"])
