"""Public jit'd entry points for the CSRC SpMV/SpMM kernels.

``SpmvOperator`` executes an :class:`repro.core.plan.ExecutionPlan` through
an :class:`repro.core.schedule.SpmvSchedule` — the precomputed artifact
bundling the pack, row partition/halo ranges, and coloring the plan needs
(core/schedule.py).  The operator never packs, partitions, or colors
inline: it asks the schedule layer (and, given ``cache=``, reuses the
artifact stored next to the plan in the tuner's PlanCache).

Dispatch is registry-driven: the plan's path resolves to its
:class:`~repro.core.paths.KernelPath` entry, whose executor factories
produce the SpMV and SpMM callables — this module contains no per-path
``if`` chain, so a newly registered path executes here with zero edits.

Registered paths (core/paths.py):

  * 'kernel'   rectangular-grid block-ELL Pallas kernel when the matrix is
    banded enough to window (interpret-mode on CPU, compiled on TPU);
  * 'flat'     flat-grid block-ELL Pallas kernel — per-tile-exact k-steps,
    no cross-tile ELL padding (skewed row-length matrices);
  * 'segment'  segment-sum jnp path (any matrix, incl. the rectangular tail);
  * 'ell'      row-padded CSRC product: the paper's local-buffer strategy
    restricted to the one term that can race.  The row's own term is a
    dense reduction over slot-major (W, n) planes; only the transpose
    term is scatter-added (square matrices whose padding n·W stays under
    ``paths.ELL_PAD_MAX``·k);
  * 'colorful' the paper's §3.2 color-by-color permutation writes, over the
    schedule's precomputed per-color slot batches.

Every path accepts ``x`` of shape (m,) — classic SpMV — or (m, r) —
multi-RHS SpMM (batched serving, block-Krylov solvers).  Construction
accepts either a fully-resolved plan (``from_plan``, the tuner path) or the
legacy keyword form where ``path='auto'`` resolves to
kernel-if-packable-else-segment (the paper's static fallback).  Either way
the operator *emits* the concrete plan it runs as ``op.plan`` and the
artifact as ``op.schedule``, so callers can cache, log, or replay both.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro import obs
from repro.core.csrc import CSRC
from repro.core import paths as paths_mod
from repro.core import schedule as schedule_mod
from repro.core.plan import ExecutionPlan
from repro.runtime import jit_hoisted
from . import ref


class SpmvOperator:
    """A prepared y = A·x / Y = A·X for repeated application.

    Builds (or fetches from ``cache``) the schedule once, compiles once per
    input shape with the schedule's arrays as program arguments; call like
    a function with x of shape (m,) or (m, r).  ``path`` is
    one of 'auto' | 'kernel' | 'segment' | 'colorful'; or pass ``plan=`` /
    use :meth:`from_plan` to pin every degree of freedom.
    """

    def __init__(self, M: CSRC, path: str = "auto", tm: int = 128,
                 w_cap: int = 4096, interpret=None,
                 coloring=None, k_step: int = 1024,
                 plan: Optional[ExecutionPlan] = None,
                 schedule: Optional["schedule_mod.SpmvSchedule"] = None,
                 cache=None):
        self.M = M
        self.n, self.m = M.n, M.m
        ks_sub = max(1, k_step // 128)

        if plan is None and schedule is not None:
            plan = schedule.plan
        if plan is None:
            if path == "auto":
                base = ExecutionPlan(path="kernel", tm=tm, w_cap=w_cap,
                                     k_step_sublanes=ks_sub)
                if M.is_square:
                    try:
                        schedule = schedule_mod.schedule_for(
                            M, base, cache=cache)
                        plan = base
                    except ValueError:      # bandwidth gate: static fallback
                        plan = dataclasses.replace(base, path="segment")
                else:
                    plan = dataclasses.replace(base, path="segment")
            else:
                plan = ExecutionPlan(path=path, tm=tm, w_cap=w_cap,
                                     k_step_sublanes=ks_sub)

        if schedule is None:
            # strict: an infeasible kernel plan or a square-only plan on a
            # rectangular matrix raises here (no silent fallback)
            schedule = schedule_mod.schedule_for(M, plan, cache=cache,
                                                 coloring=coloring)
        elif (schedule_mod.plan_artifact_fields(schedule.plan)
              != schedule_mod.plan_artifact_fields(plan)):
            raise ValueError(
                f"schedule was built for {schedule.plan.key()} and cannot "
                f"execute plan {plan.key()}")
        self.plan = plan
        self.path = plan.path
        self.interpret = interpret
        self._bind(M, schedule, coloring=coloring)

    def _bind(self, M: CSRC, schedule, coloring=None):
        """Install the schedule and (re)build both jit'd executors through
        the registry — shared by construction and ``update_values``."""
        with obs.span("kernels.bind", path=self.path, strategy="local"):
            obs.count("spmv_bind_total", path=self.path, strategy="local")
            self.M = M
            self.schedule = schedule
            self.pack = next(
                (pk for pk in (schedule.pack, schedule.flat_pack,
                               schedule.nnzsplit_pack) if pk is not None),
                None)
            self.coloring = (schedule.coloring if coloring is None
                             else coloring)

            # registry dispatch: the path's KernelPath entry builds both
            # executors from the schedule artifact (no per-path if chain)
            try:
                entry = paths_mod.get_path(self.path)
            except KeyError as e:
                raise ValueError(str(e)) from None
            spmv_fn = entry.make_spmv(
                M, schedule, self.plan, interpret=self.interpret,
                coloring=coloring)
            if entry.make_spmm is entry.make_spmv:
                # one factory registered for both shapes (e.g. colorful):
                # construct once, share the executor
                spmm_fn = spmv_fn
            else:
                spmm_fn = entry.make_spmm(
                    M, schedule, self.plan, interpret=self.interpret,
                    coloring=coloring)
            self._fn = jit_hoisted(spmv_fn)
            self._fn_mm = jit_hoisted(spmm_fn)

    def update_values(self, M: CSRC) -> "SpmvOperator":
        """Value-refresh fast path: swap in a matrix with **identical
        structure** (FEM time stepping — re-assembled values on a fixed
        connectivity).  Only the schedule's value streams are refreshed
        (``schedule.refresh_schedule``); no re-pack, no re-partition, no
        re-coloring — ``BUILD_COUNTS`` records a single ``value_refresh``.
        Raises ValueError when the structure actually differs."""
        refreshed = schedule_mod.refresh_schedule(self.schedule, M)
        self._bind(M, refreshed)
        return self

    @classmethod
    def from_plan(cls, M: CSRC, plan: ExecutionPlan,
                  interpret=None, coloring=None, cache=None,
                  schedule=None) -> "SpmvOperator":
        """Strict construction: the plan's path is executed as given (a
        'kernel' plan whose window does not fit raises ValueError).  Pass
        ``cache=`` (a PlanCache) to reuse the stored schedule artifact."""
        return cls(M, interpret=interpret, coloring=coloring, plan=plan,
                   cache=cache, schedule=schedule)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if x.ndim == 2:
            return self._fn_mm(x)
        return self._fn(x)

    def lower(self, x: jnp.ndarray):
        """The lowered program ``self(x)`` runs (HLO inspection)."""
        return (self._fn_mm if x.ndim == 2 else self._fn).lower(x)

    @property
    def flops_per_call(self) -> int:
        """Useful flops (paper §4.1): n mul + (nnz-n) fma = 2·nnz - n."""
        return 2 * self.M.nnz - self.M.n

    @property
    def bytes_per_call(self) -> int:
        if self.pack is not None:
            return self.pack.streamed_bytes()
        return self.M.working_set_bytes()


def spmv(M: CSRC, x: jnp.ndarray, path: str = "auto",
         interpret=None,
         plan: Optional[ExecutionPlan] = None) -> jnp.ndarray:
    """One-shot convenience wrapper."""
    return SpmvOperator(M, path=path, interpret=interpret, plan=plan)(x)


def spmv_transpose(M: CSRC, x: jnp.ndarray) -> jnp.ndarray:
    """A^T·x — the paper's O(1) transpose (swap al/au)."""
    return ref.csrc_spmv_transpose(M, x)


def spmm(M: CSRC, X: jnp.ndarray) -> jnp.ndarray:
    """Multi-RHS product (batched serving path)."""
    return ref.csrc_spmm(M, X)
