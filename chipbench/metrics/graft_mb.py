"""Bytes over the host link per graft, in MB: the program's count of the
trees fetched (``d2h_bytes``) and placed (``h2d_bytes``) in each
``serve.graft`` span."""


def read(ctx):
    grafts = ctx.spans.of("serve.graft", ctx.win.t_open, ctx.win.t_close)
    if not grafts:
        return None
    moved = sum(info["d2h_bytes"] + info["h2d_bytes"]
                for _, _, _, info in grafts)
    return moved / len(grafts) / 1e6
