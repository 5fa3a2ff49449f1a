"""The decode program's share of its roofline: the least time the chips
need for the traced decode steps (the larger of their bytes over peak
bandwidth and their operations over peak compute, counted by the
configuration's reference module for live rows at their own context
lengths) over the device time the steps took."""

from chipbench import flops


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["program_s"]:
        return None
    calls = ctx.spans.of("executor.decode", ctx.win.t_open, ctx.win.t_stop)
    ref, conf = ctx.cell.ref, ctx.cell.conf
    least = sum(flops.least_time(ref.decode_flops(conf, lens),
                                 ref.decode_bytes(conf, lens),
                                 ctx.peaks, ctx.cell.chips)
                for _, _, _, lens in calls)
    return 100.0 * least / tr["program_s"]
