"""Pluggable SpMV executors: how a registered matrix actually computes.

The serving engine (serve/engine.py) resolves an
:class:`~repro.core.plan.ExecutionPlan` per matrix and hands execution to
whichever executor the plan's ``strategy`` field names:

* :class:`LocalExecutor` — ``strategy='local'``: today's single-device
  :class:`~repro.kernels.ops.SpmvOperator`, schedule-cached through the
  PlanCache (zero pack/partition/coloring on a hit).

* :class:`MeshExecutor` — ``strategy='mesh'``: the paper's accumulation
  strategies across ``plan.mesh_p`` shards via
  :func:`~repro.core.distributed.build_sharded_spmv`.  Every structural
  artifact the mesh needs — the :class:`~repro.core.schedule.SpmvSchedule`
  (row partition) and the per-shard layout (``ShardedSlots`` /
  ``HaloLayout`` for segment shard-compute, the path's ShardSupport
  layouts — ``FlatShards``/``FlatHalo``, ``NnzSplitShards``/
  ``NnzSplitHalo`` — for kernel-backed paths) — is built through the
  schedule layer and, given a cache,
  served from / shipped to the PlanCache npz layer keyed by
  (fingerprint, value digest, p, strategy kind): a worker process
  re-registering a known matrix performs zero per-shard pack work.

Both executors expose the same three-method surface (``__call__``,
``update_values``, ``plan``), so the engine's coalesced multi-RHS step
path is executor-agnostic: a request batch is answered by one SpMM
through whichever executor the plan chose.

``update_values`` is the FEM time-stepping / model-refresh fast path on
either side: the local executor refreshes the schedule's value streams
(``BUILD_COUNTS['value_refresh']``), the mesh executor additionally
refreshes the shard layout's value streams
(``BUILD_COUNTS['shard_value_refresh']``) — no re-pack, no re-partition,
no re-coloring on either path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.csrc import CSRC
from repro.core.plan import ExecutionPlan


class SpmvExecutor:
    """Executor surface the serving engine programs against."""

    kind: str = "abstract"
    plan: ExecutionPlan

    @property
    def path(self) -> str:
        """Shard-compute path of the plan (SpmvOperator API parity)."""
        return self.plan.path

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def update_values(self, M: CSRC) -> "SpmvExecutor":
        raise NotImplementedError


class LocalExecutor(SpmvExecutor):
    """Single-device execution through a tuned SpmvOperator."""

    kind = "local"

    def __init__(self, M: CSRC, plan: ExecutionPlan, cache=None,
                 interpret=None):
        from repro.kernels.ops import SpmvOperator
        self.M = M
        self.op = SpmvOperator.from_plan(M, plan, interpret=interpret,
                                         cache=cache)
        self.plan = self.op.plan

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.op(x)

    def update_values(self, M: CSRC) -> "LocalExecutor":
        self.M = M
        self.op.update_values(M)
        return self

    @property
    def schedule(self):
        return self.op.schedule


class MeshExecutor(SpmvExecutor):
    """Distributed execution across ``plan.mesh_p`` shards.

    Construction materializes (or fetches from the cache's npz layer) the
    schedule and the per-shard layout, then compiles one shard_map'd
    apply through :func:`~repro.core.distributed.build_sharded_spmv` with
    the layout injected.  ``update_values`` refreshes value streams in
    place — schedule and layout — and recompiles the apply; the matrix
    structure, partition, halo geometry, and index streams never move.
    """

    kind = "mesh"

    def __init__(self, M: CSRC, plan: ExecutionPlan, mesh=None,
                 cache=None, interpret=None, axis: str = "rows"):
        if plan.strategy != "mesh":
            raise ValueError(
                f"MeshExecutor needs a strategy='mesh' plan, got "
                f"{plan.key()}")
        p = plan.mesh_p
        if mesh is None:
            ndev = len(jax.devices())
            if ndev < p:
                raise ValueError(
                    f"plan {plan.key()} needs {p} devices, this process "
                    f"sees {ndev}; relaunch with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={p} or "
                    "register a local plan")
            from repro.core.distributed import make_mesh
            mesh = make_mesh(p, axis)
        self.plan = plan
        self.mesh = mesh
        self.axis = axis
        self.p = p
        self.cache = cache
        self.interpret = interpret
        from repro.core import paths as paths_mod
        self._sup = paths_mod.get_path(plan.path).shard_support
        self._sched = None
        self.layout = None
        self._structure_digest = None
        self._diag = None
        # rows of the vectors a mesh solve runs on: n padded to p shards
        self.n_rows = -(-M.n // p) * p
        self._build(M)

    # the schedule artifact only supplies the row partition here; a
    # shard-supported plan ('flat', 'nnzsplit') builds its per-shard
    # sub-packs instead of the (unused) full-matrix pack, so the schedule
    # request is path-free
    def _sched_plan(self) -> ExecutionPlan:
        return (dataclasses.replace(self.plan, path="segment")
                if self._sup is not None else self.plan)

    def _build(self, M: CSRC):
        from repro.core import distributed as dist
        from repro.core import schedule as schedule_mod
        self.M = M
        self._structure_digest = schedule_mod.structure_digest(M)
        strat = self.plan.accumulation
        if strat == "halo":
            # halo geometry depends only on (matrix, p): no schedule needed
            self._sched = None
            if self._sup is not None:
                self.layout = schedule_mod.build_path_halo(
                    M, self.p, self.plan, cache=self.cache)
            else:
                self.layout = schedule_mod.build_halo_layout(
                    M, self.p, cache=self.cache)
        else:
            self._sched = schedule_mod.schedule_for(
                M, self._sched_plan(), cache=self.cache, p=self.p)
            part = self._sched.partition
            if self._sup is not None:
                self.layout = schedule_mod.build_path_shards(
                    M, part, self.plan, cache=self.cache)
            else:
                self.layout = schedule_mod.build_sharded_slots(
                    M, part, cache=self.cache)
        self._fn = dist.build_sharded_spmv(
            M, self.mesh, self.axis, strategy=strat, schedule=self._sched,
            cache=self.cache, plan=self.plan, interpret=self.interpret,
            layout=self.layout)
        obs.count("spmv_bind_total", path=self.plan.path, strategy=strat)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        # reduce_scatter pads y to p equal intervals; serve the true rows
        return self._fn(x)[:self.M.n]

    def apply_rows(self, x: jnp.ndarray) -> jnp.ndarray:
        """A·x on vectors of ``n_rows`` rows (a mesh solve's): the padding
        rows are read as zeros and come out as zeros."""
        n = self.M.n
        if self.n_rows == n:
            return self(x)
        pad = ((0, self.n_rows - n),) + ((0, 0),) * (x.ndim - 1)
        return jnp.pad(self(x[:n]), pad)

    def place(self, v) -> jnp.ndarray:
        """``v`` ((n,) or (n, r)) as a mesh solve's vector: zero-padded to
        ``n_rows`` rows and row-sharded over the mesh.  An array already
        so placed is returned as it is; any other is put on the mesh and
        its bytes counted in ``mesh_place_bytes_total{site="vector"}``."""
        spec = P(self.axis) if v.ndim == 1 else P(self.axis, None)
        target = NamedSharding(self.mesh, spec)
        if (isinstance(v, jax.Array) and v.shape[0] == self.n_rows
                and v.sharding.is_equivalent_to(target, v.ndim)):
            return v
        if v.shape[0] != self.n_rows:
            v = jnp.pad(v, ((0, self.n_rows - v.shape[0]),)
                        + ((0, 0),) * (v.ndim - 1))
        obs.count("mesh_place_bytes_total", v.size * v.dtype.itemsize,
                  site="vector")
        return jax.device_put(v, target)

    def diagonal(self) -> jnp.ndarray:
        """The matrix diagonal as a mesh solve's vector, placed once."""
        if self._diag is None:
            self._diag = self.place(self.M.ad)
        return self._diag

    def sharded_operands(self):
        """The device arrays the shard_map consumes, one shard per mesh
        device — what a placement check inspects."""
        return tuple(self._fn.operands)

    def update_values(self, M: CSRC) -> "MeshExecutor":
        """Same-structure value refresh on the mesh: schedule value
        streams (via the cache's structure-digest fast path) and shard
        layout value streams are rewritten; partition, halo geometry, and
        index streams are reused untouched.  Raises ValueError when the
        structure actually differs (same contract as the local path's
        ``refresh_schedule``) — the shard layouts can only be value-
        refilled against the slot order they were built for."""
        from repro.core import distributed as dist
        from repro.core import schedule as schedule_mod
        if schedule_mod.structure_digest(M) != self._structure_digest:
            raise ValueError(
                "MeshExecutor.update_values: matrix structure differs "
                "from the registered one; re-register for a full rebuild")
        part = None
        if self._sched is not None:
            if self.cache is not None:
                self._sched = schedule_mod.schedule_for(
                    M, self._sched_plan(), cache=self.cache, p=self.p)
            else:
                self._sched = schedule_mod.refresh_schedule(self._sched, M)
            part = self._sched.partition
        self.layout = schedule_mod.refresh_shard_layout(
            self.layout, M, part=part)
        self.M = M
        self._diag = None
        self._fn = dist.build_sharded_spmv(
            M, self.mesh, self.axis, strategy=self.plan.accumulation,
            schedule=self._sched, cache=self.cache, plan=self.plan,
            interpret=self.interpret, layout=self.layout)
        obs.count("spmv_bind_total", path=self.plan.path,
                  strategy=self.plan.accumulation)
        return self

    @property
    def schedule(self):
        return self._sched


def mesh_executor_for(M: CSRC, plan: ExecutionPlan, cache=None,
                      interpret=None) -> MeshExecutor:
    """The placed :class:`MeshExecutor` of (M, plan), kept across calls.

    With a cache the executor is kept in ``cache.mesh_executors``, keyed
    as its shard layouts are (fingerprint, value digest, and the plan,
    which holds p): a second call on the same matrix places no shard
    array again.  One executor is kept per (fingerprint, plan); a matrix
    of that class with other values replaces it.  Without a cache every
    call builds and places afresh."""
    if cache is None:
        return MeshExecutor(M, plan, interpret=interpret)
    from repro.core.schedule import value_digest
    from repro.core.tuner import fingerprint
    key, digest = (fingerprint(M), plan.key()), value_digest(M)
    held = cache.mesh_executors.get(key)
    if held is None or held[0] != digest:
        cache.mesh_executors.pop(key, None)     # free the old placement
        held = cache.mesh_executors[key] = (
            digest, MeshExecutor(M, plan, cache=cache, interpret=interpret))
    return held[1]
