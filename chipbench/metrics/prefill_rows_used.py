"""Useful rows of the padded prefill: admitted rows over the rows the
prefill program ran (``max_batch``), summed over the ``serve.prefill``
spans in the window."""


def read(ctx):
    pre = ctx.spans.of("serve.prefill", ctx.win.t_open, ctx.win.t_close)
    if not pre:
        return None
    return 100.0 * sum(info["rows"] for _, _, _, info in pre) / sum(
        info["padded_rows"] for _, _, _, info in pre)
