"""Window seconds over completed solves: the window closes with the first
solve that ends at or after its length, so all work and all time count."""


def read(ctx):
    return getattr(ctx, "solve_s", None)
