"""FEM time stepping: each step assembles the global matrix from the next
element-matrix set of a ring made on the device at set-up, then solves
``(M + K_t) u_t = M u_{t-1} + f`` with ``cg_solve``.

Traffic keys: ``ring`` (element-matrix sets), ``tol``, ``maxiter``,
``sample`` (steps the check compares), ``burst`` (assemblies in the traced
burst).  The configuration module gives ``mesh``, ``element_tables``,
``kappa_ring``, ``lumped_mass`` and ``reference``.
"""
from __future__ import annotations

import gc
import time
from pathlib import Path

import numpy as np

import common
from cost import assembly_bytes


class Runner:
    def __init__(self, cell, cache_dir: Path, entries=None):
        from repro.assembly import scatter
        from repro.core import solvers
        entries = entries or {}
        self.cell, self.cfg, self.t = cell, cell.config, cell.traffic
        self.cache_path = Path(cache_dir) / "plans.json"
        self.assemble = entries.get("assemble", scatter.assemble)
        self.cg_solve = entries.get("cg_solve", solvers.cg_solve)

    # -- set-up ------------------------------------------------------------
    def setup_static(self):
        import jax.numpy as jnp
        from repro.assembly import assembly_schedule_for
        from repro.core import tuner
        mod = self.cell.config_mod
        self.coords, self.conn, etype = mod.mesh(self.cfg)
        self.nn = self.coords.shape[0]
        tables, self.mass_entry = mod.element_tables(self.cfg)
        self.cache = tuner.PlanCache(path=str(self.cache_path))
        self.sched = assembly_schedule_for(self.conn, cache=self.cache,
                                           num_nodes=self.nn)
        self._etype = jnp.asarray(etype.astype(np.int32))
        self._tables = jnp.asarray(tables)
        self.mass = jnp.asarray(mod.lumped_mass(self.cfg, self.conn,
                                                self.nn).astype(np.float32))

    def setup_seed(self, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.assembly import tune_assembly
        self.seed = seed
        rng = common.rng_of(seed)
        ring = int(self.t["ring"])
        self.kappa = self.cell.config_mod.kappa_ring(self.cfg, rng, ring)
        eye = jnp.eye(4, dtype=jnp.float32)

        def make_ring(kappa, etype, tables, mass_entry):
            return (kappa[:, :, None, None] * tables[etype][None]
                    + mass_entry * eye)

        ke = jax.jit(make_ring)(jnp.asarray(self.kappa), self._etype,
                                self._tables, jnp.float32(self.mass_entry))
        self.ke = [ke[i] for i in range(ring)]
        self.src = jnp.asarray(rng.standard_normal(self.nn)
                               .astype(np.float32))
        self._rhs = jax.jit(lambda m, u, f: m * u + f)
        self.tuned = tune_assembly(self.sched, self.ke[0], cache=self.cache)
        self.u = jnp.zeros(self.nn, jnp.float32)
        self.cands = common.f32_candidates(self._assemble(0))
        for k in range(2):        # the first call tunes on a cold cache
            self._step(k)
        self.u = jnp.zeros(self.nn, jnp.float32)

    def _assemble(self, k: int):
        return self.assemble(self.sched, self.ke[k % len(self.ke)],
                             strategy=self.tuned.strategy,
                             variant=self.tuned.variant)

    def _step(self, k: int):
        import jax
        with jax.profiler.TraceAnnotation("bench.assemble"):
            M = self._assemble(k)
        b = self._rhs(self.mass, self.u, self.src)
        with jax.profiler.TraceAnnotation("bench.cg_solve"):
            res, self.op = self.cg_solve(M, b, cache=self.cache,
                                         autotune=True,
                                         candidates=self.cands,
                                         tol=self.t["tol"],
                                         maxiter=self.t["maxiter"])
            jax.block_until_ready(res.x)
        self.u = res.x
        return M, b, res

    def describe(self) -> dict:
        return {"nodes": self.nn, "elements": int(self.conn.shape[0]),
                "slots": self.sched.k,
                "assembly": self.tuned.key(), "plan": common.plan_key(self.op)}

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float):
        keep = common.Reservoir(int(self.t["sample"]),
                                common.rng_of(self.seed, 2))
        self.steps = []
        t0 = t_prev = time.perf_counter()
        deadline = t0 + seconds
        while True:
            k = len(self.steps)
            M, b, res = self._step(k)
            t_end = time.perf_counter()
            self.steps.append((int(res.iters), bool(res.converged),
                               t_end - t_prev))
            t_prev = t_end
            keep.offer(lambda: (k % len(self.ke), M, b, res.x))
            if t_end >= deadline:
                break
        self.window_s = t_end - t0
        self.kept = keep.items

    def window_summary(self) -> dict:
        return {"steps": len(self.steps), "plan": common.plan_key(self.op),
                "iters": [s[0] for s in self.steps],
                "seconds": [round(s[2], 4) for s in self.steps]}

    def fill(self, ctx):
        ctx.window_s = self.window_s
        ctx.step_s = self.window_s / len(self.steps)
        ctx.cg_iters = [s[0] for s in self.steps]

    def bursts(self) -> dict:
        n, k = self.sched.n, self.sched.k
        ne, nen = self.conn.shape
        return {"assemble": (lambda: self._assemble(0).al,
                             int(self.t["burst"]),
                             assembly_bytes(ne, nen, n, k))}

    # -- the check ----------------------------------------------------------
    def collect(self):
        self.kept = [(s, common.scipy_of(M), np.asarray(b), np.asarray(x))
                     for s, M, b, x in self.kept]

    def release(self):
        self.ke = self.op = self.cache = self.sched = self.u = None
        gc.collect()

    def check(self):
        refs = {}
        asm, res = 0.0, 0.0
        for slot, A, b, x in self.kept:
            if slot not in refs:
                refs[slot] = self.cell.config_mod.reference(
                    self.cfg, self.coords, self.conn, self.kappa[slot])
            asm = max(asm, common.matrix_rel_err(A, refs[slot]))
            res = max(res, common.rel_residual(refs[slot], x, b))
        failed = sum(not ok for _, ok, _ in self.steps)
        lim = self.cell.limits
        return ([("assembly_err", asm, lim["assembly_err"]),
                 ("residual_f64", res, lim["residual_f64"])],
                len(self.steps), failed)
