"""Repeated solves: ``cg_solve`` on a fresh right-hand side each call,
from a ring made on the device at set-up.

Traffic keys: ``ring`` (right-hand sides in the ring), ``tol`` and
``maxiter`` (``tol`` 0 runs exactly ``maxiter`` iterations a solve, as
HPCG's sets of 50 do), ``burst`` (products in the traced SpMV burst).
The configuration module gives ``arrays(cfg)`` (CSRC arrays),
``rhs_ring(cfg, rng, ring)`` (right-hand sides on the device) and
``reference(cfg)`` (float64 scipy matrix, built only for the check).
"""
from __future__ import annotations

import gc
import time
from pathlib import Path

import numpy as np

import common
from cost import csrc_spmv_bytes


class Runner:
    def __init__(self, cell, cache_dir: Path, entries=None):
        from repro.core import solvers
        self.cell, self.cfg, self.t = cell, cell.config, cell.traffic
        self.cache_path = Path(cache_dir) / "plans.json"
        self.cg_solve = (entries or {}).get("cg_solve", solvers.cg_solve)

    # -- set-up ------------------------------------------------------------
    def setup_static(self):
        import jax
        import jax.numpy as jnp
        from repro.core import csrc, tuner
        n, ia, ja, ad, al, au = self.cell.config_mod.arrays(self.cfg)
        self.M = csrc.from_assembly(n, ia, ja, ad, al, au)
        self.cache = tuner.PlanCache(path=str(self.cache_path))
        self.cands = common.f32_candidates(self.M)
        self.n, self.k = n, int(ja.shape[0])
        self.A64 = None             # the reference, built for the check
        # one solve of b = 0 runs no iteration and compiles (or loads)
        # every program the window's solves run; on a cold plan cache it
        # tunes first
        res, self.op = self._solve(jnp.zeros(n, jnp.float32))
        jax.block_until_ready(res.x)

    def _solve(self, b):
        return self.cg_solve(self.M, b, cache=self.cache, autotune=True,
                             candidates=self.cands, tol=self.t["tol"],
                             maxiter=self.t["maxiter"])

    def setup_seed(self, seed: int):
        import jax
        ring = self.cell.config_mod.rhs_ring(self.cfg, common.rng_of(seed),
                                             int(self.t["ring"]))
        self.rhs = [ring[i] for i in range(ring.shape[0])]
        jax.block_until_ready(self.rhs)

    def describe(self) -> dict:
        return {"n": self.n, "nnz": self.n + 2 * self.k,
                "plan": common.plan_key(self.op), "candidates": len(self.cands)}

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float):
        import jax
        ring = len(self.rhs)
        self.solves, self.solve_s = [], []
        t0 = t_prev = time.perf_counter()
        deadline = t0 + seconds
        while True:
            i = len(self.solves) % ring
            with jax.profiler.TraceAnnotation("bench.cg_solve"):
                res, self.op = self._solve(self.rhs[i])
                jax.block_until_ready(res.x)
            t_end = time.perf_counter()
            self.solves.append((i, res.x, int(res.iters),
                                self._ok(res)))
            self.solve_s.append(t_end - t_prev)
            t_prev = t_end
            if t_end >= deadline:
                break
        self.window_s = t_end - t0

    def _ok(self, res) -> bool:
        """Converged, or with ``tol`` 0 ran its ``maxiter`` iterations."""
        if self.t["tol"] == 0:
            return int(res.iters) == int(self.t["maxiter"])
        return bool(res.converged)

    def window_summary(self) -> dict:
        return {"solves": len(self.solves), "plan": common.plan_key(self.op),
                "iters": [s[2] for s in self.solves],
                "seconds": [round(t, 4) for t in self.solve_s]}

    def fill(self, ctx):
        ctx.window_s = self.window_s
        ctx.solve_s = self.window_s / len(self.solves)
        ctx.cg_iters = [s[2] for s in self.solves]

    def bursts(self) -> dict:
        op, b = self.op, self.rhs[0]
        return {"spmv": (lambda: op(b), int(self.t["burst"]),
                         csrc_spmv_bytes(self.n, self.k))}

    # -- the check ----------------------------------------------------------
    def collect(self):
        self.solves = [(i, np.asarray(x), it, ok)
                       for i, x, it, ok in self.solves]
        self.b_host = {i: np.asarray(self.rhs[i]) for i, *_ in self.solves}

    def release(self):
        self.M = self.op = self.cache = self.rhs = None
        gc.collect()

    def check(self):
        if self.A64 is None:
            self.A64 = self.cell.config_mod.reference(self.cfg)
        worst = max(common.rel_residual(self.A64, x, self.b_host[i])
                    for i, x, _, _ in self.solves)
        failed = sum(not ok for *_, ok in self.solves)
        return ([("residual_f64", worst,
                  self.cell.limits["residual_f64"])],
                len(self.solves), failed)
