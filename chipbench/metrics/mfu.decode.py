"""The decode step's share of the chips' peak: model operations of the
traced decode steps (real rows at their own context lengths, counted by
the configuration's reference module) over the device time the decode
program took in the trace times the chips' peak.  It bounds what a
kernel's roofline share can claim for the same step."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["program_s"]:
        return None
    calls = ctx.spans.of("executor.decode", ctx.win.t_open, ctx.win.t_stop)
    ref = ctx.cell.ref
    work = sum(ref.decode_flops(ctx.cell.conf, lens)
               for _, _, _, lens in calls)
    return 100.0 * work / (tr["program_s"] * ctx.cell.chips
                           * ctx.peaks["bf16_flops_per_s"])
