"""``cg_solve(mesh_p=p)``: CG on a p-device mesh through the tuner's mesh
plans and the serving engine's ``MeshExecutor``.

In-process tests run on a one-wide mesh (any host has one device); the
four-device test runs in a subprocess with its own XLA_FLAGS, as every
multi-device test here does (the device count locks at first jax init).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
import scipy.sparse as sp

from repro import obs
from repro.core import csrc, solvers, tuner
from repro.core.plan import ExecutionPlan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STRATEGIES = ("halo", "reduce_scatter", "allreduce")


def stencil27(nx: int, ny: int, nz: int):
    """HPCG's 27-point stencil on an nx·ny·nz grid (26 on the diagonal, -1
    to every neighbour in the 3x3x3 box), as CSRC and float64 scipy."""
    def ones3(m):
        return sp.diags([1.0, 1.0, 1.0], [-1, 0, 1], shape=(m, m))
    box = sp.kron(sp.kron(ones3(nz), ones3(ny)), ones3(nx)).tocsr()
    A = (27.0 * sp.identity(nx * ny * nz) - box).tocsr()
    return csrc.from_scipy(A), A


def rhs(A, seed: int = 0):
    x = np.random.default_rng(seed).standard_normal(A.shape[0])
    return jnp.asarray((A @ x).astype(np.float32))


def rel_residual(A, x, b) -> float:
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - A @ np.asarray(x, np.float64))
                 / np.linalg.norm(b))


def mesh_plan(acc: str, p: int = 1) -> ExecutionPlan:
    return ExecutionPlan(path="segment", partition="nnz", accumulation=acc,
                         strategy="mesh", mesh_p=p)


def local_solve(M, b, maxiter=30):
    return solvers.cg_solve(M, b, plan=ExecutionPlan(path="segment"),
                            tol=0.0, maxiter=maxiter)[0]


@pytest.mark.parametrize("acc", STRATEGIES)
def test_one_wide_mesh_matches_local_route(acc):
    M, A = stencil27(5, 4, 6)
    b = rhs(A)
    want = local_solve(M, b)
    res, ex = solvers.cg_solve(M, b, plan=mesh_plan(acc), mesh_p=1,
                               cache=tuner.PlanCache(), tol=0.0, maxiter=30)
    assert ex.plan.accumulation == acc and int(res.iters) == 30
    x, x_ref = np.asarray(res.x), np.asarray(want.x)
    assert np.abs(x - x_ref).max() <= 1e-5 * np.abs(x_ref).max()
    r, r_ref = rel_residual(A, x, b), rel_residual(A, x_ref, b)
    assert r < 1e-4 and abs(r - r_ref) <= 1e-6


def test_mesh_plan_resolves_through_mesh_plan_for():
    M, A = stencil27(5, 4, 6)
    b = rhs(A)
    cache = tuner.PlanCache()
    cands = [mesh_plan(acc) for acc in STRATEGIES]
    res, ex = solvers.cg_solve(M, b, mesh_p=1, cache=cache, autotune=True,
                               candidates=cands, tol=0.0, maxiter=20)
    assert ex.plan.key().endswith(":mesh1")
    fp = tuner.mesh_fingerprint(tuner.fingerprint(M), 1)
    entry = cache.entries[fp]
    assert sorted(entry["timings_us"]) == sorted(c.key() for c in cands)
    assert rel_residual(A, res.x, b) == pytest.approx(
        rel_residual(A, local_solve(M, b, 20).x, b), abs=1e-6)
    # the heuristic route keeps the decision under the same key
    plan = tuner.mesh_plan_for(M, 1, cache=cache)
    assert plan == ex.plan


def test_second_call_places_nothing():
    M, A = stencil27(5, 4, 6)
    cache = tuner.PlanCache()
    s0 = obs.snapshot()
    res, ex = solvers.cg_solve(M, np.asarray(rhs(A)), mesh_p=1,
                               cache=cache, tol=0.0, maxiter=5)
    d = obs.snapshot().diff(s0)
    assert d.total("mesh_place_bytes_total", site="layout") > 0
    # b from the host; on a one-wide mesh the diagonal is in place already
    assert d.value("mesh_place_bytes_total", site="vector") == 4 * M.n
    assert d.total("spmv_bind_total", strategy=ex.plan.accumulation) == 1
    b = ex.place(rhs(A, seed=1))
    s1 = obs.snapshot()
    res2, ex2 = solvers.cg_solve(M, b, mesh_p=1, cache=cache, tol=0.0,
                                 maxiter=5)
    d = obs.snapshot().diff(s1)
    assert ex2 is ex
    assert d.total("mesh_place_bytes_total") == 0
    assert d.total("spmv_bind_total") == 0
    # other values in the same class: a new executor replaces the old
    M2 = csrc.CSRC(**{f: getattr(M, f) for f in (
        "n", "m", "ia", "ja", "al", "au", "iar", "jar", "ar")},
        ad=M.ad * 2, numerically_symmetric=True)
    _, ex3 = solvers.cg_solve(M2, b, mesh_p=1, cache=cache, tol=0.0,
                              maxiter=5)
    assert ex3 is not ex and len(cache.mesh_executors) == 1


def _tree(recs, parent=None):
    kids = sorted((r for r in recs if r["parent_id"] == parent),
                  key=lambda r: r["t0"])
    return [(r["name"], r["labels"], _tree(recs, r["id"])) for r in kids]


def test_mesh_solve_span_tree_and_labels():
    M, A = stencil27(5, 4, 6)
    cache = tuner.PlanCache()
    res, ex = solvers.cg_solve(M, rhs(A), mesh_p=1, cache=cache, tol=0.0,
                               maxiter=3)
    b = ex.place(rhs(A, seed=2))
    obs.clear_trace()
    s0 = obs.snapshot()
    solvers.cg_solve(M, b, mesh_p=1, cache=cache, tol=0.0, maxiter=3)
    # warm call: the fingerprint and value digest are the matrix's memo
    bind = {"path": ex.plan.path, "strategy": ex.plan.accumulation}
    assert _tree(obs.trace()) == [
        ("solver.cg_solve", {"mesh_p": "1"}, [
            ("tune.resolve", {}, []),
            ("kernels.bind", bind, []),
            ("solver.place", {}, []),
            ("solver.dispatch", {}, [])])]
    d = obs.snapshot().diff(s0)
    assert d.value("digest_memo_total", kind="fingerprint",
                   outcome="hit") == 2
    assert d.value("digest_memo_total", kind="value", outcome="hit") == 1


@pytest.mark.parametrize("acc", STRATEGIES)
def test_warm_mesh_solve_digests_nothing(acc):
    M, A = stencil27(4, 4, 4)
    cache = tuner.PlanCache()
    plan = mesh_plan(acc)
    solvers.cg_solve(M, rhs(A), plan=plan, mesh_p=1, cache=cache,
                     tol=0.0, maxiter=2)
    s0 = obs.snapshot()
    solvers.cg_solve(M, rhs(A, seed=1), plan=plan, mesh_p=1, cache=cache,
                     tol=0.0, maxiter=2)
    d = obs.snapshot().diff(s0)
    assert d.total("digest_memo_total", outcome="miss") == 0
    assert d.total("digest_memo_total", outcome="hit") == 2
    assert d.total("host_copy_bytes_total") == 0


def test_local_route_binds_with_strategy_local():
    M, A = stencil27(4, 4, 4)
    obs.clear_trace()
    local_solve(M, rhs(A), maxiter=2)
    (bind,) = obs.trace("kernels.bind")
    assert bind["labels"] == {"path": "segment", "strategy": "local"}
    (root,) = obs.trace("solver.cg_solve")
    assert root["labels"] == {}


def test_mesh_plan_of_other_width_is_refused():
    M, A = stencil27(4, 4, 4)
    with pytest.raises(ValueError, match="mesh plan"):
        solvers.cg_solve(M, rhs(A), plan=mesh_plan("halo", p=2), mesh_p=1)
    with pytest.raises(ValueError, match="mesh plan"):
        solvers.cg_solve(M, rhs(A), plan=ExecutionPlan(path="segment"),
                         mesh_p=1)


def test_placed_vectors_are_row_sharded_and_padded():
    M, A = stencil27(4, 4, 4)
    _, ex = solvers.cg_solve(M, rhs(A), mesh_p=1, tol=0.0, maxiter=1)
    v = ex.place(np.ones(M.n, np.float32))
    assert v.shape == (ex.n_rows,) and isinstance(v, jax.Array)
    assert ex.place(v) is v
    V = ex.place(np.ones((M.n, 3), np.float32))
    assert V.shape == (ex.n_rows, 3)


FOUR_DEVICES = """
    import importlib.util, sys
    import numpy as np, jax, jax.numpy as jnp
    from repro import obs
    from repro.core import solvers, tuner
    from repro.core.plan import ExecutionPlan
    sys.path.insert(0, {tests!r})
    from test_mesh_solve import stencil27, rhs, rel_residual, mesh_plan
    assert len(jax.devices()) == 4
    for dims in ((6, 5, 12), (6, 5, 13)):
        M, A = stencil27(*dims)
        b = rhs(A)
        want, _ = solvers.cg_solve(M, b, plan=ExecutionPlan(path="segment"),
                                   tol=0.0, maxiter=30)
        x_ref = np.asarray(want.x)
        r_ref = rel_residual(A, x_ref, b)
        for acc in {strategies!r}:
            cache = tuner.PlanCache()
            res, ex = solvers.cg_solve(M, b, plan=mesh_plan(acc, 4),
                                       mesh_p=4, cache=cache, tol=0.0,
                                       maxiter=30)
            bp = ex.place(b)
            s0 = obs.snapshot()
            res, ex2 = solvers.cg_solve(M, bp, plan=mesh_plan(acc, 4),
                                        mesh_p=4, cache=cache, tol=0.0,
                                        maxiter=30)
            d = obs.snapshot().diff(s0)
            assert ex2 is ex and d.total("mesh_place_bytes_total") == 0
            x = np.asarray(res.x)
            assert x.shape == (M.n,) and int(res.iters) == 30
            err = np.abs(x - x_ref).max() / np.abs(x_ref).max()
            assert err <= 1e-5, (dims, acc, err)
            r = rel_residual(A, x, b)
            assert abs(r - r_ref) <= 1e-6, (dims, acc, r, r_ref)
            print(M.n, acc, err, r)
    # the tuner's choice among the strategies, on the 4-way mesh
    M, A = stencil27(6, 5, 12)
    cache = tuner.PlanCache()
    res, ex = solvers.cg_solve(
        M, rhs(A), mesh_p=4, cache=cache, autotune=True, tol=0.0,
        maxiter=30, candidates=[mesh_plan(a, 4) for a in {strategies!r}])
    assert ex.plan.key().endswith(":mesh4"), ex.plan.key()
    assert tuner.mesh_fingerprint(tuner.fingerprint(M), 4) in cache.entries
    print("OK", ex.plan.key())
"""


def test_four_device_mesh_cg_matches_one_device():
    """Every strategy on a 4-way mesh, on a 6x5x12 grid (n = 360) and a
    6x5x13 one (n = 390, not divisible by 4): the iterates match the
    one-device solve and the float64 residual."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = textwrap.dedent(FOUR_DEVICES).format(
        tests=os.path.join(ROOT, "tests"), strategies=STRATEGIES)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().splitlines()[-1].startswith("OK")
