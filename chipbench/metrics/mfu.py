"""Model operations completed in the window over what the chips could do
in it at peak: every prompt whose first token came in the window and
every decode step that ended in it, real rows only, counted by the
configuration's reference module."""


def read(ctx):
    t0, t1, conf = ctx.win.t_open, ctx.win.t_close, ctx.cell.conf
    ref = ctx.cell.ref
    work = sum(ref.decode_flops(conf, lens)
               for _, _, _, lens in ctx.spans.of("executor.decode", t0, t1))
    work += sum(ref.prefill_flops(conf, p)
                for _, _, _, plens in ctx.spans.of("executor.prefill", t0, t1)
                for p in plens)
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * work / ((t1 - t0) * peak)
