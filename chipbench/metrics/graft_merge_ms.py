"""Host time per graft spent merging the admitted rows into the resident
cache on the host: the program's ``serve.graft.merge`` span."""


def read(ctx):
    parts = ctx.spans.of("serve.graft.merge", ctx.win.t_open,
                         ctx.win.t_close)
    if not parts:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in parts) / len(parts)
