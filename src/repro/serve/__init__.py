"""The serving subsystem (docs/DESIGN.md §6).

  engine.py     the paper's SpMV-as-a-service (``SpmvServingEngine``),
                which coalesces same-matrix requests into one
                multi-RHS SpMM per tick
  executor.py   pluggable execution behind a registered matrix:
                ``LocalExecutor`` (single-device SpmvOperator) and
                ``MeshExecutor`` (distributed strategies over mesh_p
                shards, artifacts shipped via the PlanCache npz layer)
  placement.py  plan resolution (local vs per-(matrix, p) mesh cache
                entries) and executor construction
"""
from .engine import SpmvRequest, SpmvResult, SpmvServingEngine
from .executor import LocalExecutor, MeshExecutor, SpmvExecutor

__all__ = [
    "SpmvRequest", "SpmvResult", "SpmvServingEngine",
    "LocalExecutor", "MeshExecutor", "SpmvExecutor",
]
