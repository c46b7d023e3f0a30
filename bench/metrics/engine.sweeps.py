"""``engine.sweeps``: iterations of the Memento lookup's outer and inner
loops per batch, each one round of dependent table gathers over the whole
batch, counted on the device (the ``engine.memento.sweeps`` histogram,
one observation a batch, over the window)."""


def read(ctx):
    n, total = ctx.hist("engine.memento.sweeps")
    return total / n if n else None
