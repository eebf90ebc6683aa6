"""Iterative solvers on top of the CSRC SpMV engine.

The paper motivates SpMV as the dominant kernel of FEM iterative solvers
("a thousand products ... a reasonable value for iterative solvers like the
preconditioned conjugate gradient method and the generalized minimum
residual method").  We provide the two solver families its benchmark models:

  * cg        — preconditioned conjugate gradient (numerically symmetric
                positive-definite matrices; Jacobi preconditioner);
  * bicgstab  — for structurally-symmetric but numerically non-symmetric
                matrices (uses the O(1) CSRC transpose when needed).

Both are jax.lax.while_loop-based (jit-able end to end, dry-run lowerable)
and accept any ``spmv`` callable — single-chip kernel or the distributed
shard_map product — so the whole paper stack composes.

Multi-RHS: ``b`` may be (n,) or (n, r).  With a block of right-hand sides
the iterations run per column (independent alpha/beta per RHS) but share
one batched SpMM per step — the memory-bound matrix pass is amortized
across the block exactly as in block-Krylov methods, and the SpMV operator
(kernels/ops.py) executes it through its tuned plan.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs


class SolveResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray
    residual: jnp.ndarray         # max over RHS columns for block solves
    converged: jnp.ndarray


def _dot(a, b):
    """Per-column vdot: () for (n,) operands, (r,) for (n, r)."""
    return jnp.sum(a * b, axis=0)


def _norm(v):
    return jnp.sqrt(_dot(v, v))


def cg(spmv: Callable, b: jnp.ndarray, x0: Optional[jnp.ndarray] = None,
       tol: float = 1e-6, maxiter: int = 1000,
       diag: Optional[jnp.ndarray] = None) -> SolveResult:
    """Jacobi-preconditioned CG.  ``diag`` enables the preconditioner.
    ``b`` of shape (n, r) solves all r systems with one SpMM per step."""
    x0 = jnp.zeros_like(b) if x0 is None else x0
    inv_d = None if diag is None else jnp.where(diag != 0, 1.0 / diag, 1.0)
    if inv_d is not None and b.ndim == 2:
        inv_d = inv_d[:, None]

    def prec(r):
        return r if inv_d is None else inv_d * r

    r0 = b - spmv(x0)
    z0 = prec(r0)
    p0 = z0
    rz0 = _dot(r0, z0)
    bnorm = jnp.maximum(_norm(b), 1e-30)

    def res_of(r):
        return jnp.max(_norm(r) / bnorm)

    def cond(state):
        _, r, _, _, k, _ = state
        return (res_of(r) > tol) & (k < maxiter)

    def body(state):
        # runs only while the loop is traced: one count a trace
        obs.count("solver_traces_total")
        x, r, p, rz, k, _ = state
        ap = spmv(p)
        alpha = rz / jnp.maximum(_dot(p, ap), 1e-30)
        x = x + alpha * p
        r = r - alpha * ap
        z = prec(r)
        rz_new = _dot(r, z)
        beta = rz_new / jnp.maximum(rz, 1e-30)
        p = z + beta * p
        return (x, r, p, rz_new, k + 1, res_of(r))

    x, r, _, _, k, res = jax.lax.while_loop(
        cond, body, (x0, r0, p0, rz0, jnp.zeros((), jnp.int32),
                     res_of(r0)))
    return SolveResult(x=x, iters=k, residual=res, converged=res <= tol)


def bicgstab(spmv: Callable, b: jnp.ndarray,
             x0: Optional[jnp.ndarray] = None, tol: float = 1e-6,
             maxiter: int = 1000) -> SolveResult:
    """BiCGSTAB for non-symmetric systems; per-column scalars on (n, r)."""
    x0 = jnp.zeros_like(b) if x0 is None else x0
    r0 = b - spmv(x0)
    bnorm = jnp.maximum(_norm(b), 1e-30)
    ones = jnp.ones(b.shape[1:][:1] or ())
    init = (x0, r0, r0, ones, ones, ones,
            jnp.zeros_like(b), jnp.zeros_like(b),
            jnp.zeros((), jnp.int32), jnp.max(_norm(r0) / bnorm))

    def cond(s):
        return (s[-1] > tol) & (s[-2] < maxiter)

    def safe_div(a, d):
        # sign-preserving guard: BiCGSTAB denominators may be negative
        return a / jnp.where(jnp.abs(d) < 1e-30,
                             jnp.where(d < 0, -1e-30, 1e-30), d)

    def body(s):
        x, r, rh, rho, alpha, omega, v, p, k, _ = s
        rho_new = _dot(rh, r)
        beta = safe_div(rho_new, rho) * safe_div(alpha, omega)
        p = r + beta * (p - omega * v)
        v = spmv(p)
        alpha = safe_div(rho_new, _dot(rh, v))
        s_vec = r - alpha * v
        t = spmv(s_vec)
        omega = safe_div(_dot(t, s_vec), _dot(t, t))
        x = x + alpha * p + omega * s_vec
        r = s_vec - omega * t
        return (x, r, rh, rho_new, alpha, omega, v, p, k + 1,
                jnp.max(_norm(r) / bnorm))

    out = jax.lax.while_loop(cond, body, init)
    x, k, res = out[0], out[-2], out[-1]
    return SolveResult(x=x, iters=k, residual=res, converged=res <= tol)


def cg_solve(M, b: jnp.ndarray, *, plan=None, cache=None,
             autotune: bool = False, interpret=None,
             x0: Optional[jnp.ndarray] = None, tol: float = 1e-6,
             maxiter: int = 1000, precondition: bool = True,
             **tune_kw) -> Tuple[SolveResult, object]:
    """Matrix-level CG: builds the SpMV operator through the plan/tuner
    subsystem instead of a hard-coded path.

    Resolution order: an explicit ``plan`` wins; else the plan-cache /
    tuner (``autotune=True`` measures candidates, ``False`` uses the
    measurement-free heuristic; either way a cache hit skips everything,
    including the schedule artifact — no re-pack).  ``b`` of shape (n, r)
    runs block CG through one batched SpMM per iteration.  Returns
    ``(SolveResult, operator)`` — the operator exposes the concrete plan
    it ran as ``op.plan`` and the artifact as ``op.schedule``.
    """
    from repro.core import tuner as _tuner
    from repro.kernels.ops import SpmvOperator

    with obs.span("solver.cg_solve"):
        if plan is None:
            plan = _tuner.plan_for(M, cache=cache, autotune=autotune,
                                   interpret=interpret, **tune_kw)
        op = SpmvOperator.from_plan(M, plan, interpret=interpret,
                                    cache=cache)
        with obs.span("solver.dispatch"):
            res = cg(op, b, x0=x0, tol=tol, maxiter=maxiter,
                     diag=M.ad if precondition else None)
    return res, op
