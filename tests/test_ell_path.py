"""The row-padded 'ell' path: agreement with the segment-sum oracle on the
matrix classes it serves, the on-device value refresh, the padding gate,
re-tuning over a plan-cache entry that never measured it, and the bind
counter that says it ran."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest
import scipy.sparse as sp

from repro import obs
from repro.assembly import mesh as amesh
from repro.assembly import assemble, build_assembly_schedule
from repro.core import csrc, paths, schedule as S, tuner
from repro.core.plan import ExecutionPlan
from repro.kernels import ops, ref
from repro.kernels.csrc_spmv_ell import pack_ell

ELL = ExecutionPlan(path="ell")


def _stencil27(nx: int) -> csrc.CSRC:
    """HPCG's 27-point stencil on an nx³ grid: 26 on the diagonal, -1 to
    every neighbour in the 3x3x3 box."""
    t = sp.diags([1.0, 1.0, 1.0], [-1, 0, 1], shape=(nx, nx))
    a = sp.kron(sp.kron(t, t), t).tocsr()
    return csrc.from_scipy(27.0 * sp.identity(nx ** 3) - a)


def _tet(mass: float = 0.5, nx: int = 5):
    mesh = amesh.grid_tet(nx)
    sched = build_assembly_schedule(mesh)
    return assemble(sched, amesh.poisson_stiffness(mesh, mass=mass))


def _nonsymmetric() -> csrc.CSRC:
    M = csrc.poisson2d(7)
    rng = np.random.default_rng(3)
    return dataclasses.replace(
        M, al=jnp.asarray(rng.standard_normal(M.k).astype(np.float32)),
        au=jnp.asarray(rng.standard_normal(M.k).astype(np.float32)),
        numerically_symmetric=False)


def _empty_lower_rows(n: int = 40) -> csrc.CSRC:
    """Tridiagonal, cut before every fourth row: those rows hold no lower
    slot."""
    off = np.where(np.arange(1, n) % 4 == 0, 0.0, -1.0)
    A = sp.diags([off, np.full(n, 4.0), 0.5 * off], [-1, 0, 1]).tocsr()
    A.eliminate_zeros()
    return csrc.from_scipy(A)


def _diagonal() -> csrc.CSRC:
    return csrc.from_scipy(sp.diags(np.arange(1.0, 17.0)).tocsr())


MATRICES = [
    ("stencil27", lambda: _stencil27(6)),
    ("tet_p1", _tet),
    ("nonsymmetric", _nonsymmetric),
    ("empty_lower_rows", _empty_lower_rows),
    ("diagonal", _diagonal),
]


@pytest.mark.parametrize("nrhs", [1, 3])
@pytest.mark.parametrize("name,make", MATRICES, ids=[n for n, _ in MATRICES])
def test_ell_matches_segment_oracle(name, make, nrhs):
    M = make()
    assert any(p.path == "ell"
               for p in tuner.enumerate_plans(tuner.stats_of(M)))
    op = ops.SpmvOperator.from_plan(M, ELL)
    pk = op.schedule.ell_pack
    assert (pk.au is None) == M.numerically_symmetric
    assert pk.ja.shape == (pk.width, M.n)
    rng = np.random.default_rng(5)
    if nrhs == 1:
        x = jnp.asarray(rng.standard_normal(M.n).astype(np.float32))
        want = ref.csrc_spmv(M, x)
    else:
        x = jnp.asarray(rng.standard_normal((M.n, nrhs)).astype(np.float32))
        want = ref.csrc_spmm(M, x)
    np.testing.assert_allclose(np.asarray(op(x)), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(op(x), np.float64),
        csrc.to_dense(M).astype(np.float64) @ np.asarray(x, np.float64),
        rtol=1e-4, atol=1e-4)


def _bind_count(path: str) -> float:
    return obs.snapshot().value("spmv_bind_total", path=path,
                                strategy="local")


@pytest.mark.parametrize("second", ["symmetric", "nonsymmetric"])
def test_ell_refresh_equals_rebuild(second):
    """A same-structure refresh re-pads the values on the device and gives
    the planes a rebuild gives, with no structural work; a refresh to a
    matrix that is no longer numerically symmetric brings ``au`` back."""
    M0 = _tet(mass=0.5)
    if second == "symmetric":
        M1 = _tet(mass=1.5)
    else:
        rng = np.random.default_rng(9)
        M1 = dataclasses.replace(
            M0, au=jnp.asarray(rng.standard_normal(M0.k)
                               .astype(np.float32)),
            numerically_symmetric=False)
    cache = tuner.PlanCache()
    op = ops.SpmvOperator.from_plan(M0, ELL, cache=cache)
    before = dict(S.BUILD_COUNTS)
    binds = _bind_count("ell")
    op.update_values(M1)
    after = dict(S.BUILD_COUNTS)
    delta = {k: after[k] - before.get(k, 0) for k in after
             if after[k] != before.get(k, 0)}
    assert delta == {"value_refresh": 1}, delta
    assert _bind_count("ell") == binds + 1
    got, want = op.schedule.ell_pack, pack_ell(M1)
    assert got.width == want.width
    for f in ("ja", "al", "au", "plane_of_slot"):
        a, b = getattr(got, f), getattr(want, f)
        assert (a is None) == (b is None), f
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x = jnp.asarray(np.random.default_rng(2).standard_normal(M1.n)
                    .astype(np.float32))
    np.testing.assert_allclose(np.asarray(op(x)),
                               np.asarray(ref.csrc_spmv(M1, x)),
                               rtol=1e-5, atol=1e-5)


def test_ell_gate_refuses_skewed_matrix():
    M = csrc.skewed_band(256, 48, 3, seed=1)
    stats = tuner.stats_of(M)
    assert M.n * stats.lower_row_max > paths.ELL_PAD_MAX * M.k
    assert not any(p.path == "ell" for p in tuner.enumerate_plans(stats))
    with pytest.raises(ValueError, match="pad more than"):
        ops.SpmvOperator.from_plan(M, ELL)
    with pytest.raises(ValueError):
        ops.SpmvOperator.from_plan(csrc.rectangular_fem(48, 16, 4, seed=5),
                                   ELL)


def test_stale_plan_cache_entry_tunes_again(tmp_path):
    """An entry measured over a pool without 'ell', or from before the
    pool was recorded, is a miss for a pool that offers 'ell'."""
    M = _stencil27(6)
    pool = tuner.enumerate_plans(tuner.stats_of(M))
    old_pool = [p for p in pool if p.path != "ell"]
    path = str(tmp_path / "plans.json")
    cache = tuner.PlanCache(path=path)

    def measure(op, x):
        return 1.0 if op.plan.path == "ell" else 2.0

    res = tuner.tune(M, cache=cache, candidates=old_pool, measure=measure)
    assert not res.cached and res.plan.path != "ell"
    again = tuner.tune(M, cache=cache, candidates=old_pool, measure=measure)
    assert again.cached
    fresh = tuner.tune(M, cache=cache, candidates=pool, measure=measure)
    assert not fresh.cached and fresh.plan.path == "ell"
    assert "ell" in cache.entries[fresh.fingerprint]["pool_paths"]

    # an entry written before the pool was recorded: reload, tune again
    del cache.entries[fresh.fingerprint]["pool_paths"]
    cache.save()
    reloaded = tuner.PlanCache(path=path)
    res = tuner.tune(M, cache=reloaded, measure=measure)
    assert not res.cached and res.plan.path == "ell"
    assert tuner.tune(M, cache=reloaded, measure=measure).cached


def test_bind_counter_counts_each_bind():
    M = _stencil27(6)
    ell0, seg0 = _bind_count("ell"), _bind_count("segment")
    op = ops.SpmvOperator.from_plan(M, ELL)
    op.update_values(M)
    ops.SpmvOperator.from_plan(M, ExecutionPlan(path="segment"))
    assert _bind_count("ell") == ell0 + 2
    assert _bind_count("segment") == seg0 + 1
    rec = [r for r in obs.trace("kernels.bind")][-3:]
    assert [r["labels"]["path"] for r in rec] == ["ell", "ell", "segment"]
