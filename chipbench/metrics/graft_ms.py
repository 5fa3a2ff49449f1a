"""Host time per graft of admitted rows into the resident cache: the
program's ``serve.graft`` span (fetch to the host, merge there, place
back until ``device_put`` returns)."""


def read(ctx):
    grafts = ctx.spans.of("serve.graft", ctx.win.t_open, ctx.win.t_close)
    if not grafts:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in grafts) / len(grafts)
