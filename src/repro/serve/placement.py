"""Placement: which executor (and which plan) serves a registered matrix.

One decision point for the serving engine:

* ``resolve_plan`` — the plan a matrix will run with.  With a requested
  mesh width it consults the per-(matrix, p) mesh entries of the plan
  cache (``tuner.mesh_plan_for``: cache hit > measured ``tune_mesh`` when
  autotuning > collective-bytes heuristic); without one — or when the
  process cannot see enough devices — it degrades to the local entries
  (``tuner.plan_for``).  Either way the decision is cached, so it is
  stable across engines and processes.

* ``build_executor`` — the executor for a resolved plan:
  ``strategy='mesh'`` plans get a :class:`~repro.serve.executor.
  MeshExecutor` over a ``plan.mesh_p``-wide mesh, everything else a
  :class:`~repro.serve.executor.LocalExecutor`.

Device counts are locked at first jax init: a CPU host serves meshes only
when launched with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(the 8-device CI smoke job and examples/serve_mesh.py do exactly that).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.csrc import CSRC
from repro.core.plan import ExecutionPlan

from .executor import LocalExecutor, MeshExecutor, SpmvExecutor


def device_count() -> int:
    return len(jax.devices())


def mesh_available(p: Optional[int]) -> bool:
    return p is not None and p >= 1 and device_count() >= p


def resolve_plan(M: CSRC, cache=None, autotune: bool = False,
                 interpret=None,
                 mesh_p: Optional[int] = None,
                 nrhs: int = 1) -> ExecutionPlan:
    """The plan to serve this matrix with, honoring a mesh request when
    the process can satisfy it and falling back to local otherwise.
    Rectangular matrices always resolve locally — the distributed
    strategies shard square rows only.

    ``nrhs`` > 1 is the engine's batched operating point: autotuning then
    measures every candidate at nrhs=1 *and* at that block width (argmin
    on per-column time), so the cached winner is tuned for the coalesced
    SpMM the engine actually issues — the winning ``plan.nrhs`` records
    the width it was tuned at."""
    from repro.core import tuner
    tune_kw = {}
    if autotune and nrhs > 1:
        tune_kw["nrhs_options"] = (1, nrhs)
    if mesh_p is not None and mesh_available(mesh_p) and M.is_square:
        return tuner.mesh_plan_for(M, mesh_p, cache=cache,
                                   autotune=autotune, interpret=interpret,
                                   **tune_kw)
    return tuner.plan_for(M, cache=cache, autotune=autotune,
                          interpret=interpret, **tune_kw)


def build_executor(M: CSRC, plan: ExecutionPlan, cache=None,
                   interpret=None, mesh=None,
                   axis: str = "rows") -> SpmvExecutor:
    """Executor for a resolved plan (strategy field dispatch)."""
    if plan.strategy == "mesh":
        return MeshExecutor(M, plan, mesh=mesh, cache=cache,
                            interpret=interpret, axis=axis)
    return LocalExecutor(M, plan, cache=cache, interpret=interpret)
