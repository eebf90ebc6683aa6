"""Per cent of the traced window in which no operation ran on the device
(busy = union of the device's op intervals)."""
from tracereduce import idle_percent


def read(ctx):
    return idle_percent(ctx)
