"""Pieces the runners share: seeds, the float32 candidate pool, the
float64 view of a CSRC matrix, and the comparisons the checks make."""
from __future__ import annotations

from typing import List

import numpy as np


def rng_of(seed: int, stream: int = 0) -> np.random.Generator:
    """A numpy generator for one stream of a run's seed (any size)."""
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def f32_candidates(M) -> list:
    """The tuner's pool without reduced-precision value streams: the
    configurations state float32, and a bfloat16 matrix would solve
    another matrix than the one the reference checks.  None for a matrix
    that is not a CSRC one (the control's)."""
    from repro.core import tuner
    if not hasattr(M, "ja"):
        return None
    return [p for p in tuner.enumerate_plans(tuner.stats_of(M))
            if p.value_dtype == "float32"]


def scipy_of(M):
    """float64 scipy CSR of a square CSRC matrix, from its arrays alone."""
    import scipy.sparse as sp
    if sp.issparse(M):
        return M.tocsr().astype(np.float64)
    ia = np.asarray(M.ia, np.int64)
    rows = np.repeat(np.arange(M.n, dtype=np.int64), np.diff(ia))
    ja = np.asarray(M.ja, np.int64)
    diag = np.arange(M.n, dtype=np.int64)
    r = np.concatenate([diag, rows, ja])
    c = np.concatenate([diag, ja, rows])
    v = np.concatenate([np.asarray(M.ad, np.float64),
                        np.asarray(M.al, np.float64),
                        np.asarray(M.au, np.float64)])
    return sp.csr_matrix((v, (r, c)), shape=(M.n, M.n))


def rel_residual(A64, x, b) -> float:
    """||b - A x|| / ||b|| in float64."""
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - A64 @ x) / np.linalg.norm(b))


def matrix_rel_err(A, A_ref) -> float:
    """Largest entry of |A - A_ref| over the largest of |A_ref|."""
    d = (A - A_ref).tocsr()
    worst = float(np.abs(d.data).max()) if d.nnz else 0.0
    return worst / float(np.abs(A_ref.data).max())


class Reservoir:
    """A uniform sample of at most ``k`` items from a stream of unknown
    length, drawn from a seeded generator."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.items: List = []

    def offer(self, item_fn):
        """``item_fn()`` makes the item, only when it is kept."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item_fn())
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.items[j] = item_fn()


def plan_key(op) -> str:
    """The execution plan an operator runs (the control's has none)."""
    plan = getattr(op, "plan", None)
    return plan.key() if plan is not None else "control"
