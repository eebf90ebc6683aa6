"""Seconds on the host clock in ``cg_solve`` a time step of the window
(the program's ``solver.cg_solve`` span: plan resolution, the schedule's
value refresh, executor binding, and the tracing and launch of the CG
loop; not the loop's device time)."""
from programspans import seconds_per_call


def read(ctx):
    return seconds_per_call(ctx, "solver.cg_solve")
