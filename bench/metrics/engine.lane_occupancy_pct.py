"""``engine.lane_occupancy_pct``: the share of the Memento loops' lanes
that still had work in an iteration, in %, over the window: Σ
``engine.memento.lane_sweeps`` ÷ (Σ ``engine.memento.sweeps`` × keys a
batch).  The rest of each sweep runs lanes that have already settled.
Nothing to read where no sweep ran."""


def read(ctx):
    _, sweeps = ctx.hist("engine.memento.sweeps")
    _, lanes = ctx.hist("engine.memento.lane_sweeps")
    if not sweeps:
        return None
    return 100.0 * lanes / (sweeps * ctx.cell.traffic["batch_keys"])
