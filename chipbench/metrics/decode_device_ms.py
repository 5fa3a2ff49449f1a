"""Device time per execution of the decode program, from the trace,
averaged over the chips."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["program_execs"]:
        return None
    return 1e3 * tr["program_s"] / tr["program_execs"]
