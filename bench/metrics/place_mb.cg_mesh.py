"""Megabytes put on the mesh a solve of the window
(``mesh_place_bytes_total``: shard layouts and solver vectors).  A solve
of a matrix whose executor is placed, on a right-hand side already
row-sharded, puts nothing there: the target is 0."""
from programspans import count_per_call


def read(ctx):
    b = count_per_call(ctx, "mesh_place_bytes_total")
    return None if b is None else b / 1e6
