"""Host time per graft spent fetching the resident cache and the prefill
cache to the host: the program's ``serve.graft.fetch`` span (it also
waits for the prefill that made the prefill cache)."""


def read(ctx):
    parts = ctx.spans.of("serve.graft.fetch", ctx.win.t_open,
                         ctx.win.t_close)
    if not parts:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in parts) / len(parts)
