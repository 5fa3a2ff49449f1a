"""Seconds the plan solve took (host clock around resolve_serve_plan)."""


def read(ctx):
    return ctx.plan_solve_s
