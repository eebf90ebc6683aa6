"""Repeated solves on a mesh of the cell's chips: the ``solve`` runner
with ``cg_solve(mesh_p=<chips>)``.

The ring of right-hand sides is made at set-up and row-sharded over the
mesh, so a solve of the window puts nothing on the mesh; the traced
burst is one product of the operator ``cg_solve`` returned (the mesh
executor), whose least bytes are one chip's share (``cost_mesh.py``).
The traffic keys and the configuration module are ``solve``'s.  The
grid's rows must split evenly over the chips.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

import common
from cost_mesh import mesh_spmv_shard_bytes
from harness import load_module

_solve = load_module(Path(__file__).with_name("solve.py"),
                     "bench_runner_solve_of_mesh_solve")


class Runner(_solve.Runner):
    # -- set-up ------------------------------------------------------------
    def setup_static(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import csrc, tuner
        from repro.core.distributed import make_mesh
        n, ia, ja, ad, al, au = self.cell.config_mod.arrays(self.cfg)
        self.M = csrc.from_assembly(n, ia, ja, ad, al, au)
        self.cache = tuner.PlanCache(path=str(self.cache_path))
        self.p = self.cell.chips
        self.n, self.k = n, int(ja.shape[0])
        rows = np.flatnonzero(np.diff(ia))
        self.band = int((rows - np.minimum.reduceat(ja, ia[rows])).max()
                        if rows.size else 0)
        self.A64 = None
        self.rows = NamedSharding(make_mesh(self.p), P("rows"))
        # one solve of b = 0 runs no iteration and compiles (or loads)
        # every program the window's solves run; on a cold plan cache
        # tune_mesh measures the strategies first
        res, self.op = self._solve(
            jax.device_put(jnp.zeros(n, jnp.float32), self.rows))
        jax.block_until_ready(res.x)

    def _solve(self, b):
        return self.cg_solve(self.M, b, cache=self.cache, autotune=True,
                             mesh_p=self.p, tol=self.t["tol"],
                             maxiter=self.t["maxiter"])

    def setup_seed(self, seed: int):
        import jax
        ring = self.cell.config_mod.rhs_ring(self.cfg, common.rng_of(seed),
                                             int(self.t["ring"]))
        self.rhs = [jax.device_put(ring[i], self.rows)
                    for i in range(ring.shape[0])]
        del ring
        jax.block_until_ready(self.rhs)

    def describe(self) -> dict:
        return {"n": self.n, "nnz": self.n + 2 * self.k, "band": self.band,
                "chips": self.p, "plan": common.plan_key(self.op)}

    # -- after the window ----------------------------------------------------
    def bursts(self) -> dict:
        op, b = self.op, self.rhs[0]
        return {"spmv": (lambda: op(b), int(self.t["burst"]),
                         mesh_spmv_shard_bytes(self.n, self.k, self.p,
                                               self.band))}
