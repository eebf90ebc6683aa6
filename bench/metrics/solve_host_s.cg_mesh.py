"""Seconds on the host clock in ``cg_solve`` a solve of the window (the
program's ``solver.cg_solve`` span: the mesh plan's resolution, the
placed executor's lookup by fingerprint and value digest, the vectors'
placement, and the tracing and launch of the CG loop, but not the loop's
device time)."""
from programspans import seconds_per_call


def read(ctx):
    return seconds_per_call(ctx, "solver.cg_solve")
