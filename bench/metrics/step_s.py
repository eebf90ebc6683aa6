"""Window seconds over completed time steps (assemble, then solve); the
window closes with the first step that ends at or after its length."""


def read(ctx):
    return getattr(ctx, "step_s", None)
