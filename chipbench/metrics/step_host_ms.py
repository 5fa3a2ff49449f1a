"""Host time per steady engine iteration with the chip idle: over the
``serve.iteration`` spans that decoded and did not prefill, the mean of
the span's duration less its ``serve.decode.wait`` (the host waiting for
the step)."""

import bisect


def read(ctx):
    its = ctx.spans.of("serve.iteration", ctx.win.t_open, ctx.win.t_close)
    waits = sorted((a, b) for name, a, b, _ in ctx.spans.rows
                   if name == "serve.decode.wait")
    prefills = sorted(a for name, a, _, _ in ctx.spans.rows
                      if name == "serve.prefill")
    starts = [a for a, _ in waits]
    host = []
    for _, a, b, _ in its:
        k = bisect.bisect_left(prefills, a)
        if k < len(prefills) and prefills[k] < b:
            continue
        j = bisect.bisect_left(starts, a)
        if j == len(waits) or waits[j][1] > b:
            continue
        host.append(b - a - (waits[j][1] - waits[j][0]))
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
