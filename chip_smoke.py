#!/usr/bin/env python3
"""Run the main path once on a TPU chip, at full size, and check the answers.

One process, public API only.  Each phase prints one line: sizes, set-up
seconds, the chosen plan and the correctness result.  Times are host
wall-clock seconds of whole phases (compilation included), not device
metrics.

  A serve     csrc.fem_band(2**20, 16) (~1.05 M rows, ~21 M non-zeros):
              SpmvServingEngine(autotune=True) tunes on the chip and
              answers 17 requests (one coalesced batch of 16, then one
              single); every y is checked against a float64 scipy CSR
              product built from the generator's arrays (max-norm
              relative error <= 1e-5).  A one-hot Pallas plan on the same
              matrix must show ``tpu_custom_call`` in its compiled HLO and
              pass the same check.
  B solve     fem_band(2**20, 16, numeric_symmetric=True) (diagonally
              dominant, SPD): cg_solve, tuned on the chip over the
              float32 candidates, to a relative residual of 1e-5; the
              residual is recomputed in float64.
  C assemble  assembly.mesh.grid_tet(48) (~118 k nodes, ~660 k tets):
              assembly_schedule_for -> tune_assembly -> assemble, bit-
              identical to scatter_serial on dyadic synthetic_stiffness;
              then one time step (assemble new values, update_values,
              cg_solve) with no structural rebuild in BUILD_COUNTS.

``--four-chips`` runs only the mesh path and what it is compared with:
tune_mesh(A, 4) and SpmvServingEngine(mesh_p=4) under the halo,
allreduce and reduce_scatter strategies, against the float64 reference
and the one-chip result, with every sharded operand spread over four
distinct devices.

The script exits non-zero without the final line when JAX finds no TPU,
when a phase raises, or when a check fails.  Otherwise its last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python3 chip_smoke.py [--four-chips] [--seed 0]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

N = 2 ** 20
HALF_BAND = 16
TET_NX = 48
SPMV_TOL = 1e-5
CG_TOL = 1e-5
CACHE_DIR = os.path.join(HERE, ".smoke_cache")


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise CheckFailed(what)


def say(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileLog:
    """Host-side compile seconds and persistent-cache hits from JAX's own
    monitoring events, read per phase."""

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"compile_s": round(self.seconds, 3),
               "cache_hits": self.hits, "cache_misses": self.misses}
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


def scipy_of(M):
    """float64 scipy CSR of a square CSRC matrix, from its arrays alone."""
    import scipy.sparse as sp
    from repro.core.csrc import row_of_slot
    rows = row_of_slot(M).astype(np.int64)
    ja = np.asarray(M.ja, np.int64)
    diag = np.arange(M.n, dtype=np.int64)
    r = np.concatenate([diag, rows, ja])
    c = np.concatenate([diag, ja, rows])
    v = np.concatenate([np.asarray(M.ad, np.float64),
                        np.asarray(M.al, np.float64),
                        np.asarray(M.au, np.float64)])
    return sp.csr_matrix((v, (r, c)), shape=(M.n, M.n))


def rel_err(y, y_ref) -> float:
    y = np.asarray(y, np.float64)
    return float(np.abs(y - y_ref).max() / max(np.abs(y_ref).max(), 1e-30))


def f32_candidates(M):
    """The tuner's pool without reduced-precision value streams: a
    bfloat16 matrix solves a different system than the one whose float64
    residual is checked."""
    from repro.core import tuner
    return [p for p in tuner.enumerate_plans(tuner.stats_of(M))
            if p.value_dtype == "float32"]


def fresh_cache():
    from repro.core import tuner
    shutil.rmtree(CACHE_DIR, ignore_errors=True)
    return tuner.PlanCache(path=os.path.join(CACHE_DIR, "plans.json"))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_serve(cache, seed: int, log: CompileLog):
    from repro.core import csrc, paths
    from repro.core.plan import ExecutionPlan
    from repro.kernels.ops import SpmvOperator
    from repro.serve import SpmvServingEngine

    t0 = time.perf_counter()
    M = csrc.fem_band(N, HALF_BAND, seed=seed)
    A64 = scipy_of(M)
    t_gen = time.perf_counter() - t0

    t0 = time.perf_counter()
    eng = SpmvServingEngine(cache=cache, autotune=True)
    plan = eng.register("A", M)
    t_reg = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    xs = [rng.standard_normal(N).astype(np.float32) for _ in range(17)]
    t0 = time.perf_counter()
    uids = [eng.submit("A", x) for x in xs[:16]]
    out = eng.run_until_drained()
    uids.append(eng.submit("A", xs[16]))
    out.update(eng.run_until_drained())
    t_serve = time.perf_counter() - t0
    check(set(out) == set(uids), "not every request was answered")
    err = max(rel_err(out[u], A64 @ x.astype(np.float64))
              for u, x in zip(uids, xs))
    check(err <= SPMV_TOL, f"served y off by {err:.3e}")
    if paths.runs_pallas(plan):
        X = jnp.asarray(np.stack(xs[:8], axis=1))
        hlo = eng.executor("A").op.lower(X).compile().as_text()
        check("tpu_custom_call" in hlo, "winner's HLO has no Pallas kernel")
    say("A serve", rows=M.n, nnz=M.nnz, gen_s=round(t_gen, 3),
        register_tune_s=round(t_reg, 3), serve_s=round(t_serve, 3),
        requests=len(uids), winner=plan.key(),
        winner_runs_pallas=paths.runs_pallas(plan),
        max_rel_err=f"{err:.3e}", **log.take())

    # a one-hot Pallas plan on the same matrix, whatever won the tune
    pplan = ExecutionPlan(path="kernel", tm=128, variant="onehot")
    t0 = time.perf_counter()
    op = SpmvOperator.from_plan(M, pplan, cache=cache)
    X = jnp.asarray(np.stack(xs[:8], axis=1))
    hlo = op.lower(X).compile().as_text()
    check("tpu_custom_call" in hlo, "one-hot plan's HLO has no Pallas kernel")
    err_v = rel_err(op(jnp.asarray(xs[0])), A64 @ xs[0].astype(np.float64))
    Y = np.asarray(op(X), np.float64)
    err_m = rel_err(Y, A64 @ np.asarray(X, np.float64))
    check(max(err_v, err_m) <= SPMV_TOL,
          f"one-hot kernel off by {max(err_v, err_m):.3e}")
    say("A pallas", plan=pplan.key(), tpu_custom_call=True,
        setup_and_run_s=round(time.perf_counter() - t0, 3),
        spmv_rel_err=f"{err_v:.3e}", spmm8_rel_err=f"{err_m:.3e}",
        **log.take())
    return M, A64


def phase_solve(cache, seed: int, log: CompileLog):
    from repro.core import csrc
    from repro.core.solvers import cg_solve

    t0 = time.perf_counter()
    M = csrc.fem_band(N, HALF_BAND, seed=seed + 1, numeric_symmetric=True)
    A64 = scipy_of(M)
    t_gen = time.perf_counter() - t0
    b = np.random.default_rng(seed + 1).standard_normal(N).astype(np.float32)
    t0 = time.perf_counter()
    res, op = cg_solve(M, jnp.asarray(b), cache=cache, autotune=True,
                       candidates=f32_candidates(M), tol=CG_TOL,
                       maxiter=500)
    x = np.asarray(res.x, np.float64)
    t_solve = time.perf_counter() - t0
    b64 = b.astype(np.float64)
    r64 = float(np.linalg.norm(b64 - A64 @ x) / np.linalg.norm(b64))
    check(bool(res.converged), "CG did not converge")
    check(r64 <= 2 * CG_TOL, f"float64 residual {r64:.3e}")
    say("B solve", rows=M.n, nnz=M.nnz, gen_s=round(t_gen, 3),
        tune_and_solve_s=round(t_solve, 3), plan=op.plan.key(),
        iters=int(res.iters), residual_f32=f"{float(res.residual):.3e}",
        residual_f64=f"{r64:.3e}", **log.take())


def phase_assemble(cache, seed: int, log: CompileLog):
    from repro.assembly import mesh as amesh
    from repro.assembly import (assemble, assembly_schedule_for,
                                scatter_serial, tune_assembly)
    from repro.core.schedule import BUILD_COUNTS
    from repro.core.solvers import cg_solve

    t0 = time.perf_counter()
    mesh = amesh.grid_tet(TET_NX)
    sched = assembly_schedule_for(mesh, cache=cache)
    t_sched = time.perf_counter() - t0
    ke = amesh.synthetic_stiffness(mesh, seed=seed)
    t0 = time.perf_counter()
    tuned = tune_assembly(sched, ke, cache=cache)
    M = assemble(sched, ke, strategy=tuned.strategy, variant=tuned.variant)
    t_asm = time.perf_counter() - t0
    got = np.concatenate([np.asarray(M.ad), np.asarray(M.al),
                          np.asarray(M.au)])
    check(np.array_equal(got, scatter_serial(sched, ke)),
          "assembled values differ from the serial oracle")
    say("C assemble", nodes=mesh.num_nodes, tets=mesh.ne, slots=sched.k,
        schedule_s=round(t_sched, 3), tune_assemble_s=round(t_asm, 3),
        winner=tuned.key(), bit_identical=True, **log.take())

    b = np.random.default_rng(seed + 2).standard_normal(M.n).astype(
        np.float32)
    res0, op = cg_solve(M, jnp.asarray(b), cache=cache, autotune=True,
                        candidates=f32_candidates(M), tol=CG_TOL,
                        maxiter=500)
    check(bool(res0.converged), "step-0 CG did not converge")
    before = dict(BUILD_COUNTS)
    t0 = time.perf_counter()
    ke1 = amesh.synthetic_stiffness(mesh, seed=seed + 1)
    M1 = assemble(sched, ke1, strategy=tuned.strategy, variant=tuned.variant)
    op.update_values(M1)
    res1, op1 = cg_solve(M1, jnp.asarray(b), cache=cache, autotune=True,
                         tol=CG_TOL, maxiter=500)
    x1 = np.asarray(res1.x)
    t_step = time.perf_counter() - t0
    delta = {k: v - before.get(k, 0) for k, v in dict(BUILD_COUNTS).items()
             if v != before.get(k, 0)}
    structural = {k: v for k, v in delta.items()
                  if k not in ("value_refresh", "assembly_value_refresh")}
    check(not structural, f"time step rebuilt structure: {structural}")
    A64 = scipy_of(M1)
    r64 = float(np.linalg.norm(b - A64 @ x1.astype(np.float64))
                / np.linalg.norm(b.astype(np.float64)))
    y_op = np.asarray(op(jnp.asarray(b)), np.float64)
    check(rel_err(y_op, A64 @ b.astype(np.float64)) <= SPMV_TOL,
          "refreshed operator does not apply the new matrix")
    check(bool(res1.converged) and r64 <= 2 * CG_TOL,
          f"step CG residual {r64:.3e}")
    say("C step", plan=op1.plan.key(), step_s=round(t_step, 3),
        iters=int(res1.iters), residual_f64=f"{r64:.3e}",
        build_delta=json.dumps(delta, sort_keys=True).replace(" ", ""),
        **log.take())


def phase_mesh(cache, seed: int, log: CompileLog, p: int = 4):
    from repro.core import csrc, tuner
    from repro.serve import SpmvServingEngine

    t0 = time.perf_counter()
    M = csrc.fem_band(N, HALF_BAND, seed=seed)
    A64 = scipy_of(M)
    t_gen = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    xs = [rng.standard_normal(N).astype(np.float32) for _ in range(8)]
    refs = [A64 @ x.astype(np.float64) for x in xs]

    # the one-chip result: a local engine on the default device
    local = SpmvServingEngine(cache=cache)
    local.register("A", M)
    uids = [local.submit("A", x) for x in xs]
    out = local.run_until_drained()
    y1 = [np.asarray(out[u], np.float64) for u in uids]
    say("mesh one-chip", rows=M.n, gen_s=round(t_gen, 3),
        plan=local.plan("A").key(), **log.take())

    t0 = time.perf_counter()
    tuned = tuner.tune_mesh(M, p, cache=cache)
    say("mesh tune", p=p, winner=tuned.plan.key(),
        candidates=len(tuned.timings_s),
        tune_s=round(time.perf_counter() - t0, 3), **log.take())
    for acc in ("halo", "allreduce", "reduce_scatter"):
        plan = dataclasses.replace(tuned.plan, accumulation=acc)
        eng = SpmvServingEngine(cache=cache, mesh_p=p)
        t0 = time.perf_counter()
        eng.register("A", M, plan=plan)
        ex = eng.executor("A")
        devices = {d.id for d in ex.mesh.devices.flat}
        check(len(devices) == p, f"{acc}: mesh spans {sorted(devices)}")
        for arr in ex.sharded_operands():
            on = {s.device.id for s in arr.addressable_shards}
            check(on == devices,
                  f"{acc}: operand {arr.shape} on devices {sorted(on)}")
        uids = [eng.submit("A", x) for x in xs]
        out = eng.run_until_drained()
        err_ref = max(rel_err(out[u], r) for u, r in zip(uids, refs))
        err_one = max(rel_err(out[u], y) for u, y in zip(uids, y1))
        check(err_ref <= SPMV_TOL, f"{acc}: off the reference {err_ref:.3e}")
        check(err_one <= SPMV_TOL, f"{acc}: off one chip {err_one:.3e}")
        say(f"mesh {acc}", plan=plan.key(), executor=out[uids[0]].executor,
            devices=sorted(devices), run_s=round(time.perf_counter() - t0, 3),
            rel_err_ref=f"{err_ref:.3e}", rel_err_one_chip=f"{err_one:.3e}",
            **log.take())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: {want} chips needed, "
              f"{len(jax.devices())} found", file=sys.stderr)
        return 2

    from repro.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()
    log = CompileLog()
    cache = fresh_cache()
    say("start", device=dev.device_kind, count=len(jax.devices()),
        compile_cache=cache_dir)
    t0 = time.perf_counter()
    if args.four_chips:
        phase_mesh(cache, args.seed, log)
    else:
        phase_serve(cache, args.seed, log)
        phase_solve(cache, args.seed, log)
        phase_assemble(cache, args.seed, log)
    say("done", total_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
