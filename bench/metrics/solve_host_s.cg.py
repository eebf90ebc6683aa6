"""Seconds on the host clock in ``cg_solve`` a solve of the window (the
program's ``solver.cg_solve`` span: plan and schedule resolution, executor
binding, and the tracing and launch of the CG loop, but not the loop's
device time, which the window waits for outside the call)."""
from programspans import seconds_per_call


def read(ctx):
    return seconds_per_call(ctx, "solver.cg_solve")
