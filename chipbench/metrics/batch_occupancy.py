"""Live rows per decode step as a share of the decode slots, from the
engine's counters (occupancy_sum, iterations) across the window."""


def read(ctx):
    (occ0, it0), (occ1, it1) = ctx.win.counters_open, ctx.win.counters_stop
    if it1 == it0:
        return None
    return 100.0 * (occ1 - occ0) / (it1 - it0) / ctx.max_batch
