"""The control of the correctness check: the plain reference put in the
program's place and computed in bfloat16, the precision below the float32
that the configurations state.  A check whose limits are sound fails it.

``entries(cell)`` gives the runners' entry points in the program's
signatures:

  cg_solve   Jacobi-preconditioned CG, every vector and product in bf16
  assemble   the COO assembly of the mesh, accumulated in bf16

Products use an ELL layout (rows padded to the longest), which gathers
and never scatters.  The benchmark's runs never use these; ``calibrate.py
--control`` and the tests do.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import common

DTYPE = "bfloat16"


class Result(NamedTuple):
    x: object
    iters: object
    residual: object
    converged: object


def ell_of(A):
    """(cols, vals) of a scipy CSR matrix, rows padded with zeros."""
    A = A.tocsr()
    n = A.shape[0]
    lens = np.diff(A.indptr)
    w = int(lens.max())
    slot = np.arange(A.nnz) - np.repeat(A.indptr[:-1], lens)
    rows = np.repeat(np.arange(n), lens)
    cols = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, w))
    vals = np.zeros((n, w), np.float32)
    cols[rows, slot] = A.indices
    vals[rows, slot] = A.data
    return cols, vals


def ell_apply(cols, vals, x):
    """y = A·x (x of shape (n,) or (n, r)) in the dtype of ``vals``."""
    xs = x.astype(vals.dtype)[cols]
    if x.ndim == 1:
        return (vals * xs).sum(axis=1)
    return (vals[:, :, None] * xs).sum(axis=1)


def _device_ell(A):
    import jax.numpy as jnp
    cols, vals = ell_of(A)
    return jnp.asarray(cols), jnp.asarray(vals).astype(DTYPE)


def _cg(cols, vals, b, tol, maxiter):
    """Jacobi-PCG in the dtype of ``vals``."""
    import jax
    import jax.numpy as jnp
    dt = vals.dtype
    n = b.shape[0]
    diag = jnp.where(cols == jnp.arange(n)[:, None], vals, 0).sum(axis=1)
    inv_d = jnp.where(diag != 0, 1 / diag, 1).astype(dt)
    b = b.astype(dt)
    bnorm = jnp.sqrt(jnp.sum(b * b))

    def cond(s):
        return (jnp.sqrt(jnp.sum(s[1] * s[1])) / bnorm > tol) & (s[4]
                                                                 < maxiter)

    def body(s):
        x, r, p, rz, k = s
        ap = ell_apply(cols, vals, p)
        alpha = rz / jnp.sum(p * ap)
        x = x + alpha * p
        r = r - alpha * ap
        z = inv_d * r
        rz_new = jnp.sum(r * z)
        return x, r, z + (rz_new / rz) * p, rz_new, k + 1

    z0 = inv_d * b
    x, r, _, _, k = jax.lax.while_loop(
        cond, body, (jnp.zeros_like(b), b, z0, jnp.sum(b * z0),
                     jnp.zeros((), jnp.int32)))
    res = jnp.sqrt(jnp.sum(r * r)) / bnorm
    return Result(x.astype(jnp.float32), k, res, res <= tol)


def entries(cell) -> dict:
    """The control's entry points for one cell."""
    import jax
    import jax.numpy as jnp
    import scipy.sparse as sp
    cg = jax.jit(_cg, static_argnames=("tol", "maxiter"))
    held = {}       # the last matrix's ELL, kept while it is in use

    def operator(M):
        if held.get("M") is not M:
            held.update(M=M, ell=_device_ell(common.scipy_of(M)))
        return held["ell"]

    def cg_solve(M, b, *, tol, maxiter, **_):
        cols, vals = operator(M)
        return (cg(cols, vals, jnp.asarray(b), tol=tol, maxiter=maxiter),
                lambda x: ell_apply(cols, vals, x))

    out = {"cg_solve": cg_solve}
    mod, cfg = cell.config_mod, cell.config
    if hasattr(mod, "mesh"):
        coords, conn, _ = mod.mesh(cfg)
        n = coords.shape[0]
        rows = np.repeat(conn, conn.shape[1], axis=1).ravel()
        cols = np.tile(conn, (1, conn.shape[1])).ravel()
        pattern = sp.csr_matrix((np.ones(rows.size), (rows, cols)),
                                shape=(n, n))
        pattern.sum_duplicates()
        keys = (np.repeat(np.arange(n, dtype=np.int64),
                          np.diff(pattern.indptr)) * n + pattern.indices)
        pos = jnp.asarray(np.searchsorted(keys, rows.astype(np.int64) * n
                                          + cols).astype(np.int32))
        scatter = jax.jit(lambda ke, pos: jnp.zeros(
            keys.size, DTYPE).at[pos].add(ke.reshape(-1).astype(DTYPE)))

        def assemble(sched, ke, **_):
            vals = np.asarray(scatter(ke, pos), np.float32)
            return sp.csr_matrix((vals, pattern.indices, pattern.indptr),
                                 shape=(n, n))

        out["assemble"] = assemble
    return out
