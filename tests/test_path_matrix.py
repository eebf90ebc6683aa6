"""The cross-path table: every registered kernel path against every square
matrix class, at one right-hand side and at a block of four.

Each case builds the path's first two exact-precision candidates that pass
its feasibility gate, through the strict ``SpmvOperator.from_plan``, and
checks the product against the float64 dense one.  A path that offers no
candidate for the matrix must refuse it the way the tuner expects: its
``feasible`` gate rejects the default plan, or the build raises the
``ValueError`` that ``tune`` skips.  A default plan that passes both is
executable, so it is checked against the dense product instead.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import csrc, paths, tuner
from repro.core.plan import ExecutionPlan, feasible
from repro.kernels.ops import SpmvOperator
from test_invariants import SQUARE_GENERATORS

PATH_NAMES = [p.name for p in paths.registered_paths()]
NRHS = (1, 4)
MAX_CANDIDATES = 2


def _plans(name, M):
    """The path's feasible exact-precision candidates (reduced-precision
    ones answer to the tuner's accuracy gate, not to this tolerance)."""
    stats = tuner.stats_of(M)
    cands = paths.get_path(name).candidates(stats, paths.CandidateSpace())
    ok = [p for p in cands
          if p.value_dtype == "float32"
          and feasible(p, n=M.n, m=M.m, bandwidth=stats.bandwidth)]
    return ok[:MAX_CANDIDATES]


def _default_op(name, M):
    """The operator of the path's default plan, or None where the path
    refuses it the way the tuner expects."""
    plan = ExecutionPlan(path=name)
    if not feasible(plan, n=M.n, m=M.m, bandwidth=csrc.bandwidth(M)):
        return None
    try:
        return SpmvOperator.from_plan(M, plan)
    except ValueError:
        return None


@pytest.mark.parametrize("nrhs", NRHS)
@pytest.mark.parametrize("gen", SQUARE_GENERATORS,
                         ids=[n for n, _ in SQUARE_GENERATORS])
@pytest.mark.parametrize("name", PATH_NAMES)
def test_path_matches_dense(name, gen, nrhs):
    M = gen[1]()
    A = csrc.to_dense(M).astype(np.float64)
    shape = (M.m,) if nrhs == 1 else (M.m, nrhs)
    x = np.random.default_rng(5).standard_normal(shape).astype(np.float32)
    y_ref = A @ x.astype(np.float64)
    scale = max(1.0, np.abs(y_ref).max())

    plans = _plans(name, M)
    if plans:
        ops = [SpmvOperator.from_plan(M, dataclasses.replace(p, nrhs=nrhs))
               for p in plans]
    else:
        op = _default_op(name, M)
        if op is None:
            return
        ops = [op]
    for op in ops:
        y = np.asarray(op(jnp.asarray(x)), dtype=np.float64)
        assert y.shape == y_ref.shape
        np.testing.assert_allclose(y / scale, y_ref / scale,
                                   rtol=2e-4, atol=2e-4)
