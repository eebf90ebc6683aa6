"""The row-padded 'ell' product on each shard of a mesh: its ShardSupport
layouts (``EllHalo`` for the halo strategy, ``EllShards`` for allreduce /
reduce_scatter) against scipy and the local route, their value refresh,
their trip through the PlanCache's npz layer, the mesh tuner's pool rule
for entries measured before 'ell' ran on a mesh, and the bind counter.

In-process tests run on a one-wide mesh or build the four-shard layouts
without devices; the four-device tests share one subprocess with its own
XLA_FLAGS (the device count locks at first jax init).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest
import scipy.sparse as sp

from repro import obs
from repro.core import csrc, schedule as S, solvers, tuner
from repro.core.plan import ExecutionPlan
from repro.kernels import csrc_spmv_ell as E
from repro.kernels import ops
from repro.serve import MeshExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STRATEGIES = ("halo", "reduce_scatter", "allreduce")
# n = 1024 divides by 4; n = 1071 does not (the last shard is short)
GRIDS = ((8, 8, 16), (9, 7, 17))
STRUCTURAL_KEYS = ("ell_pack", "partition", "schedule", "sharded_slots",
                   "halo_layout", "ell_shards", "ell_halo")


def stencil27(nx: int, ny: int, nz: int, symmetric: bool = True):
    """HPCG's 27-point stencil on an nx·ny·nz grid as CSRC and float64
    scipy; ``symmetric=False`` draws the upper values afresh (the same
    structure, ``au`` kept)."""
    def ones3(m):
        return sp.diags([1.0, 1.0, 1.0], [-1, 0, 1], shape=(m, m))
    box = sp.kron(sp.kron(ones3(nz), ones3(ny)), ones3(nx)).tocsr()
    M = csrc.from_scipy((27.0 * sp.identity(nx * ny * nz) - box).tocsr())
    if not symmetric:
        rng = np.random.default_rng(nx * ny * nz)
        M = dataclasses.replace(
            M, au=jnp.asarray(rng.standard_normal(M.k).astype(np.float32)),
            numerically_symmetric=False)
    return M, sp.csr_matrix(csrc.to_dense(M).astype(np.float64))


def mesh_plan(acc: str, p: int = 1, path: str = "ell") -> ExecutionPlan:
    return ExecutionPlan(path=path, partition="nnz", accumulation=acc,
                         strategy="mesh", mesh_p=p)


def _x(n: int, nrhs: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    shape = (n,) if nrhs == 1 else (n, nrhs)
    return rng.standard_normal(shape).astype(np.float32)


def _build_delta(fn):
    before = dict(S.BUILD_COUNTS)
    out = fn()
    after = dict(S.BUILD_COUNTS)
    return out, {k: after.get(k, 0) - before.get(k, 0)
                 for k in set(after) | set(before)
                 if after.get(k, 0) != before.get(k, 0)}


def _clear_layout_memos():
    S._SHARDED_SLOTS_MEMO.clear()
    S._HALO_LAYOUT_MEMO.clear()
    for memo in S._PATH_LAYOUT_MEMOS.values():
        memo.clear()


# ---------------------------------------------------------------------------
# One-wide mesh: the MeshExecutor machinery with the ell layouts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nrhs", [1, 3])
@pytest.mark.parametrize("symmetric", [True, False],
                         ids=["symmetric", "nonsymmetric"])
@pytest.mark.parametrize("acc", STRATEGIES)
def test_one_wide_mesh_matches_scipy_and_local_route(acc, symmetric, nrhs):
    M, A = stencil27(*GRIDS[1], symmetric=symmetric)
    ex = MeshExecutor(M, mesh_plan(acc))
    assert (ex.layout.au is None) == symmetric
    x = _x(M.n, nrhs)
    y = np.asarray(ex(jnp.asarray(x)), np.float64)
    want = A @ x.astype(np.float64)
    assert np.abs(y - want).max() <= 1e-5 * np.abs(want).max()
    local = ops.SpmvOperator.from_plan(M, ExecutionPlan(path="ell"))
    np.testing.assert_allclose(y, np.asarray(local(jnp.asarray(x))),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("acc", STRATEGIES)
def test_mesh_route_that_chose_ell_counts_its_bind(acc):
    M, A = stencil27(*GRIDS[0])
    b = jnp.asarray((A @ _x(M.n, 1)).astype(np.float32))
    before = obs.snapshot()
    res, ex = solvers.cg_solve(M, b, plan=mesh_plan(acc), mesh_p=1,
                               cache=tuner.PlanCache(), tol=0.0, maxiter=10)
    d = obs.snapshot().diff(before)
    assert ex.plan.path == "ell" and int(res.iters) == 10
    assert d.value("spmv_bind_total", path="ell", strategy=acc) == 1
    assert d.value("spmv_bind_total", path="segment", strategy=acc) == 0


# ---------------------------------------------------------------------------
# The four-shard layouts, built without devices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("grid", GRIDS, ids=["n1024", "n1071"])
def test_four_shard_layouts_cover_every_slot_once(grid):
    M, _ = stencil27(*grid)
    halo = E.pack_ell_halo(M, 4)
    part = S.partition_rows_by_nnz(M, 4)
    shards = E.pack_ell_shards(M, part.starts)
    for lay, cols in ((halo, halo.n_local), (shards, M.n)):
        assert lay.ja.shape == (4, lay.width, lay.ns)
        assert lay.width == 13 and lay.au is None
        pos = np.asarray(lay.plane_of_slot)
        assert np.unique(pos).size == M.k
        np.testing.assert_array_equal(np.asarray(lay.al).reshape(-1)[pos],
                                      np.asarray(M.al))
        ja = np.asarray(lay.ja)
        assert ja.min() >= 0 and ja.max() < cols
        # padding holds no value
        assert np.count_nonzero(np.asarray(lay.al)) == M.k
    np.testing.assert_array_equal(np.asarray(shards.row0),
                                  np.asarray(part.starts)[:-1])


@pytest.mark.parametrize("second", ["symmetric", "nonsymmetric"])
def test_four_shard_refresh_equals_rebuild(second):
    """A refresh re-pads the values through ``plane_of_slot`` and gives
    what a rebuild of the new matrix gives, index planes and shapes
    kept; a matrix no longer numerically symmetric brings ``au`` back."""
    M0, _ = stencil27(*GRIDS[1])
    M1 = (dataclasses.replace(M0, al=M0.al * 2, au=M0.au * 2, ad=M0.ad + 1)
          if second == "symmetric" else stencil27(*GRIDS[1], False)[0])
    part = S.partition_rows_by_nnz(M0, 4)
    pairs = [(E.refresh_ell_halo(E.pack_ell_halo(M0, 4), M1),
              E.pack_ell_halo(M1, 4)),
             (E.refresh_ell_shards(E.pack_ell_shards(M0, part.starts), M1,
                                   part.starts),
              E.pack_ell_shards(M1, part.starts))]
    for got, want in pairs:
        for f in dataclasses.fields(want):
            a, b = getattr(got, f.name), getattr(want, f.name)
            assert (a is None) == (b is None), f.name
            if a is not None:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=f.name)
    assert (pairs[0][0].au is None) == (second == "symmetric")


@pytest.mark.parametrize("acc", STRATEGIES)
def test_executor_value_refresh_moves_no_structural_counter(acc):
    M, _ = stencil27(*GRIDS[0])
    ex = MeshExecutor(M, mesh_plan(acc))
    M2 = dataclasses.replace(M, al=M.al * 3, au=M.au * 3, ad=M.ad * 3)
    _, d = _build_delta(lambda: ex.update_values(M2))
    assert d.get("shard_value_refresh") == 1, d
    assert not any(d.get(k) for k in STRUCTURAL_KEYS), d
    x = _x(M.n, 1, seed=4)
    want = np.asarray(csrc.to_dense(M2), np.float64) @ x
    y = np.asarray(ex(jnp.asarray(x)), np.float64)
    assert np.abs(y - want).max() <= 1e-5 * np.abs(want).max()


def test_shard_padding_gate_refuses_a_thin_first_shard():
    """On a 6x5x12 grid the whole matrix passes the padding gate, but the
    first of four shards, with no rows below it, pads 1.73 times its
    slots: both packers refuse it and the mesh tuner skips the plans."""
    M, _ = stencil27(6, 5, 12)
    stats = tuner.stats_of(M)
    assert {"ell", "segment"} <= {
        p.path for p in tuner.enumerate_mesh_plans(stats, 4)}
    with pytest.raises(ValueError, match="shard 0 pad more than"):
        E.pack_ell_halo(M, 4)
    with pytest.raises(ValueError, match="pad more than"):
        E.pack_ell_shards(M, S.partition_rows_by_nnz(M, 4).starts)


def test_mesh_pool_offers_ell_only_under_the_padding_gate():
    M, _ = stencil27(*GRIDS[0])
    plans = tuner.enumerate_mesh_plans(tuner.stats_of(M), 4)
    by_acc = {(p.path, p.accumulation) for p in plans}
    assert {("ell", a) for a in STRATEGIES} <= by_acc
    skewed = csrc.skewed_band(512, 24, 3, seed=2)
    assert "ell" not in {p.path for p in tuner.enumerate_mesh_plans(
        tuner.stats_of(skewed), 4)}


# ---------------------------------------------------------------------------
# The PlanCache npz layer and the mesh tuner's pool rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("symmetric", [True, False],
                         ids=["symmetric", "nonsymmetric"])
@pytest.mark.parametrize("acc", ["halo", "allreduce"])
def test_warm_plan_cache_serves_ell_layouts_with_zero_packs(tmp_path, acc,
                                                            symmetric):
    M, _ = stencil27(*GRIDS[0], symmetric=symmetric)
    plan = mesh_plan(acc)
    cache_file = str(tmp_path / "plans.json")
    ex = MeshExecutor(M, plan, cache=tuner.PlanCache(path=cache_file))
    x = jnp.asarray(_x(M.n, 1, seed=1))
    y_ref = np.asarray(ex(x))

    _clear_layout_memos()
    cache2 = tuner.PlanCache(path=cache_file)
    ex2, d = _build_delta(lambda: MeshExecutor(M, plan, cache=cache2))
    assert d == {}, f"shipped ell layouts were rebuilt: {d}"
    assert cache2.shard_layout_hits >= 1
    assert type(ex2.layout) is type(ex.layout)
    assert (ex2.layout.au is None) == symmetric
    np.testing.assert_array_equal(np.asarray(ex2(x)), y_ref)


@pytest.mark.parametrize("stale", ["no_pool_record", "pool_without_ell"])
def test_stale_mesh_entry_is_measured_again(tmp_path, stale):
    """A mesh entry written before the mesh pool offered 'ell' (no
    ``pool_paths``, or a pool without 'ell') is a miss for ``tune_mesh``;
    once measured over the whole pool it is a hit."""
    M, _ = stencil27(*GRIDS[0])
    path = str(tmp_path / "plans.json")
    cache = tuner.PlanCache(path=path)
    seg = [mesh_plan(a, path="segment") for a in STRATEGIES]

    def measure(fn, x):
        return 1.0

    res = tuner.tune_mesh(M, 1, cache=cache, candidates=seg, measure=measure)
    assert not res.cached and res.plan.path == "segment"
    entry = cache.entries[res.fingerprint]
    assert entry["pool_paths"] == ["segment"]
    if stale == "no_pool_record":
        del entry["pool_paths"]
    cache.save()
    reloaded = tuner.PlanCache(path=path)
    # the old pool still hits an entry that recorded it
    again = tuner.tune_mesh(M, 1, cache=reloaded, candidates=seg,
                            measure=measure)
    assert again.cached == (stale == "pool_without_ell")
    fresh = tuner.tune_mesh(M, 1, cache=reloaded, measure=measure)
    assert not fresh.cached
    assert {"ell", "segment"} <= {k.split(":")[0] for k in fresh.timings_s}
    assert "ell" in reloaded.entries[fresh.fingerprint]["pool_paths"]
    assert tuner.tune_mesh(M, 1, cache=reloaded, measure=measure).cached


def test_mesh_hit_after_tuning_computes_no_statistics(monkeypatch):
    """The probe after a tuning run (a solve's, in a window) finds the
    offered paths where the run left them: no matrix statistics on the
    call path."""
    M, _ = stencil27(*GRIDS[0])
    cache = tuner.PlanCache()
    tuner.tune_mesh(M, 1, cache=cache, measure=lambda fn, x: 1.0)
    calls = []
    real = tuner.stats_of
    monkeypatch.setattr(tuner, "stats_of",
                        lambda m: calls.append(1) or real(m))
    assert tuner.tune_mesh(M, 1, cache=cache).cached
    assert calls == []


def test_ell_layout_npz_roundtrip_keeps_absent_upper(tmp_path):
    M, _ = stencil27(*GRIDS[1])
    for lay in (E.pack_ell_halo(M, 4),
                E.pack_ell_shards(stencil27(*GRIDS[1], False)[0],
                                  S.partition_rows_by_nnz(M, 4).starts)):
        f = str(tmp_path / f"{type(lay).__name__}.npz")
        S.save_shard_layout_npz(f, lay)
        back = S.load_shard_layout_npz(f)
        assert type(back) is type(lay)
        for fld in dataclasses.fields(lay):
            a, b = getattr(lay, fld.name), getattr(back, fld.name)
            if a is None or isinstance(a, int):
                assert a == b, fld.name
            else:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Four devices: every strategy against scipy and the local route
# ---------------------------------------------------------------------------

FOUR_DEVICES = """
    import json, sys
    import numpy as np, jax, jax.numpy as jnp
    from repro import obs
    from repro.core import tuner
    from repro.core.plan import ExecutionPlan
    from repro.kernels import ops
    from repro.serve import MeshExecutor
    sys.path.insert(0, {tests!r})
    from test_ell_mesh import GRIDS, STRATEGIES, mesh_plan, stencil27, _x
    assert len(jax.devices()) == 4
    for grid in GRIDS:
        for sym in (True, False):
            M, A = stencil27(*grid, symmetric=sym)
            local = ops.SpmvOperator.from_plan(M, ExecutionPlan(path="ell"))
            for acc in STRATEGIES:
                s0 = obs.snapshot()
                ex = MeshExecutor(M, mesh_plan(acc, 4))
                binds = obs.snapshot().diff(s0).value(
                    "spmv_bind_total", path="ell", strategy=acc)
                for nrhs in (1, 3):
                    x = _x(M.n, nrhs, seed=nrhs)
                    y = np.asarray(ex(jnp.asarray(x)), np.float64)
                    want = A @ x.astype(np.float64)
                    yl = np.asarray(local(jnp.asarray(x)), np.float64)
                    print(json.dumps(dict(
                        grid=list(grid), sym=sym, acc=acc, nrhs=nrhs,
                        shape=list(y.shape), binds=binds,
                        scipy=float(np.abs(y - want).max()
                                    / np.abs(want).max()),
                        local=float(np.abs(y - yl).max()
                                    / np.abs(yl).max()))))
    # the mesh tuner measures ell against segment under each strategy
    M, _ = stencil27(*GRIDS[0])
    pool = [c for c in tuner.enumerate_mesh_plans(tuner.stats_of(M), 4)
            if c.path in ("ell", "segment")]
    res = tuner.tune_mesh(M, 4, cache=tuner.PlanCache(), candidates=pool)
    print("TUNED", json.dumps(sorted(res.timings_s)))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = textwrap.dedent(FOUR_DEVICES).format(
        tests=os.path.join(ROOT, "tests"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = out.stdout.strip().splitlines()
    assert lines[-1].startswith("TUNED")
    return ([json.loads(ln) for ln in lines[:-1]],
            json.loads(lines[-1][len("TUNED"):]))


@pytest.mark.parametrize("acc", STRATEGIES)
def test_four_device_ell_matches_scipy_and_local_route(four_devices, acc):
    rows = [r for r in four_devices[0] if r["acc"] == acc]
    assert len(rows) == len(GRIDS) * 2 * 2
    for r in rows:
        n = int(np.prod(r["grid"]))
        assert r["shape"] == ([n] if r["nrhs"] == 1 else [n, r["nrhs"]]), r
        assert r["binds"] == 1, r
        assert r["scipy"] <= 1e-5 and r["local"] <= 1e-5, r


def test_four_device_tuner_measures_ell_under_every_strategy(four_devices):
    keys = four_devices[1]
    for acc in STRATEGIES:
        for path in ("ell", "segment"):
            assert any(k.startswith(path + ":") and f":{acc}:" in k
                       for k in keys), (path, acc, keys)
