"""Megabytes copied from the device to the host a time step of the window
(``host_copy_bytes_total``: the assembled values, the tuner's
fingerprints and the schedule's digests)."""
from programspans import count_per_call


def read(ctx):
    b = count_per_call(ctx, "host_copy_bytes_total")
    return None if b is None else b / 1e6
