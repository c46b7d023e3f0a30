"""``engine.longest_key_sweeps``: the most loop iterations any one key of
a batch did work in, averaged over batches, counted on the device (the
``engine.memento.longest_lane`` histogram, one observation a batch, over
the window).  A lane-synchronous loop that let each lane carry its own
outer/inner phase would run this many sweeps; ``engine.sweeps`` over it
is what the nesting costs.  Nothing to read where the program does not
count it."""


def read(ctx):
    n, total = ctx.hist("engine.memento.longest_lane")
    return total / n if n else None
