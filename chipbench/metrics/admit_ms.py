"""Host time per call of the executor's prefill (padded prefill, graft
into the resident cache and the first token; it ends in a host sync)."""


def read(ctx):
    calls = ctx.spans.of("executor.prefill", ctx.win.t_open, ctx.win.t_close)
    if not calls:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in calls) / len(calls)
