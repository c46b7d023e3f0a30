"""``engine.outer_sweeps``: iterations of the Memento lookup's outer loop
per batch, each of which starts a fresh inner loop that runs until the
slowest chain of any lane settles, counted on the device (the
``engine.memento.outer_sweeps`` histogram, one observation a batch, over
the window).  Nothing to read where the program does not count it."""


def read(ctx):
    n, total = ctx.hist("engine.memento.outer_sweeps")
    return total / n if n else None
