"""Mean ``SolveResult.iters`` over the solves of the window (a count the
solver reports)."""
import numpy as np


def read(ctx):
    it = getattr(ctx, "cg_iters", None)
    return float(np.mean(it)) if it else None
