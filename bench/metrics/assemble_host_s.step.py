"""Seconds a time step of the window in ``values_to_csrc`` (the
program's ``assembly.to_csrc`` span: waiting for the scatter's values,
copying them to the host, splitting them and uploading the matrix)."""
from programspans import seconds_per_call


def read(ctx):
    return seconds_per_call(ctx, "assembly.to_csrc")
