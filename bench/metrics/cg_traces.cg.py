"""Traces of the CG loop a solve of the window (``solver_traces_total``,
which the loop's body counts each time it is traced)."""
from programspans import count_per_call


def read(ctx):
    return count_per_call(ctx, "solver_traces_total")
