"""Host time per graft spent placing the merged cache on the device: the
program's ``serve.graft.place`` span, until ``device_put`` returns (the
rest of the copy is waited for by the next decode step)."""


def read(ctx):
    parts = ctx.spans.of("serve.graft.place", ctx.win.t_open,
                         ctx.win.t_close)
    if not parts:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in parts) / len(parts)
