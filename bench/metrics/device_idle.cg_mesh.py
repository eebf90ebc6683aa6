"""Per cent of the traced window in which no operation ran on a chip,
averaged over the mesh's chips (busy = union of each chip's op
intervals)."""
from tracereduce import idle_percent


def read(ctx):
    return idle_percent(ctx)
