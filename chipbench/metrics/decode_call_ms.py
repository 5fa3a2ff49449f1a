"""Host time per call of the executor's decode (one step for every live
row; it ends in a host sync)."""


def read(ctx):
    calls = ctx.spans.of("executor.decode", ctx.win.t_open, ctx.win.t_close)
    if not calls:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in calls) / len(calls)
