"""The nnz-split kernel as a first-class KernelPath: registry dispatch,
tuner enumeration (unstructured-gated, feasibility-filtered), schedule
artifacts with cache/disk round-trips and zero-rebuild probes, bit-exact
multi-RHS execution vs the dense oracle under dyadic values, shard-local
nnz-split execution in every distributed strategy, and the serving engine
running a tuned nnzsplit plan.

Bit-identity discipline: the unstructured suite matrices carry small-
integer values (powerlaw_laplacian, paper_example) or are quantized to
dyadic values, and x is drawn from multiples of 1/8 — float32
accumulation of the products is then order-independent, so the chunked
kernel must match the dense oracle **bit for bit**; a dropped or
double-counted stream entry is always visible.
"""
import dataclasses
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import csrc, distributed as D, paths, schedule as S, tuner
from repro.core.plan import PATHS, ExecutionPlan, feasible
from repro.kernels import ops
from repro.kernels.csrc_spmv_nnzsplit import NnzSplitPack, pack_nnzsplit


def _unstructured(n=300, seed=0, **kw):
    return csrc.powerlaw_laplacian(n, seed=seed, **kw)


def _dyadic(M):
    def q(a):
        return jnp.asarray(np.round(np.asarray(a) * 64.0) / 64.0,
                           jnp.float32)
    return dataclasses.replace(M, ad=q(M.ad), al=q(M.al), au=q(M.au))


def _dyadic_x(m, seed=0, nrhs=None):
    rng = np.random.default_rng(seed)
    shape = (m,) if nrhs is None else (m, nrhs)
    return (rng.integers(-64, 64, shape) / 8.0).astype(np.float32)


def _check_exact(M, plan, nrhs=None, seed=11):
    """Dyadic bit-identity against the dense oracle (no tolerances)."""
    A = np.asarray(csrc.to_dense(M), np.float64)
    x = _dyadic_x(M.m, seed=seed, nrhs=nrhs)
    op = ops.SpmvOperator.from_plan(M, plan)
    assert op.plan.path == plan.path          # strict: no silent fallback
    y = np.asarray(op(jnp.asarray(x)))
    ref = (A @ x.astype(np.float64)).astype(np.float32)
    np.testing.assert_array_equal(y, ref, err_msg=f"plan {plan.key()}")
    return op


def _build_delta(fn):
    before = dict(S.BUILD_COUNTS)
    out = fn()
    after = dict(S.BUILD_COUNTS)
    return out, {k: after.get(k, 0) - before.get(k, 0)
                 for k in set(after) | set(before)
                 if after.get(k, 0) != before.get(k, 0)}


STRUCTURAL_KEYS = ("pack", "flat_pack", "nnzsplit_pack", "partition",
                   "coloring", "schedule", "sharded_slots", "halo_layout",
                   "flat_shards", "flat_halo", "nnzsplit_shards",
                   "nnzsplit_halo")


# ---------------------------------------------------------------------------
# Registry + plan layer
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_nnzsplit_is_a_registered_path(self):
        assert "nnzsplit" in PATHS
        entry = paths.get_path("nnzsplit")
        assert entry.name == "nnzsplit"
        plan = ExecutionPlan(path="nnzsplit", k_step_sublanes=4)
        assert plan.key().startswith("nnzsplit:ks4")

    def test_plan_key_is_tm_independent(self):
        """Chunking is row-independent: tm is not a degree of freedom."""
        a = ExecutionPlan(path="nnzsplit", tm=32, k_step_sublanes=4)
        b = ExecutionPlan(path="nnzsplit", tm=128, k_step_sublanes=4)
        assert a.key() == b.key()
        assert S.plan_artifact_fields(a) == S.plan_artifact_fields(b)

    def test_square_only_and_int16_gate(self):
        plan = ExecutionPlan(path="nnzsplit")
        assert feasible(plan, n=64, m=64, bandwidth=10)
        assert not feasible(plan, n=64, m=96, bandwidth=10)
        i16 = ExecutionPlan(path="nnzsplit", index_dtype="int16")
        assert feasible(i16, n=32767, m=32767, bandwidth=10)
        assert not feasible(i16, n=32768, m=32768, bandwidth=10)

    def test_shard_support_registered(self):
        """The tentpole claim: mesh serving needs no per-path edits — the
        registry entry itself carries the shard-compute hooks."""
        sup = paths.get_path("nnzsplit").shard_support
        assert sup is not None
        assert sup.shards_kind == "nnzsplit_shards"
        assert sup.halo_kind == "nnzsplit_halo"


class TestEnumeration:
    def test_emitted_on_unstructured_matrices(self):
        M = _unstructured()
        stats = tuner.stats_of(M)
        assert paths.nnzsplit_worth_measuring(stats), "not unstructured?"
        plans = tuner.enumerate_plans(stats)
        cand = [p for p in plans if p.path == "nnzsplit"]
        assert cand, [p.key() for p in plans]
        assert len({p.k_step_sublanes for p in cand}) > 1  # ks sweep
        for p in cand:
            assert feasible(p, n=M.n, m=M.m, bandwidth=stats.bandwidth)

    def test_skipped_on_banded_low_skew_matrices(self):
        """poisson2d and the skewed band (CoV ~1.5, narrow band) stay with
        the windowed paths — nnzsplit's gate is deliberately above flat's
        skew floor."""
        for M in (csrc.poisson2d(16), csrc.skewed_band(256, 48, 3, seed=1)):
            stats = tuner.stats_of(M)
            assert not paths.nnzsplit_worth_measuring(stats)
            assert not any(p.path == "nnzsplit"
                           for p in tuner.enumerate_plans(stats))

    def test_rectangular_matrix_yields_no_nnzsplit(self):
        M = csrc.rectangular_fem(48, 16, 4, seed=5)
        plans = tuner.enumerate_plans(tuner.stats_of(M))
        assert all(p.path == "segment" for p in plans)
        with pytest.raises(ValueError):
            ops.SpmvOperator.from_plan(M, ExecutionPlan(path="nnzsplit"))

    def test_r_cap_gate_raises_in_packer(self):
        """A stream whose chunks span row windows beyond r_cap belongs to
        the banded paths; the packer refuses instead of padding."""
        M = _unstructured(600, seed=2)
        with pytest.raises(ValueError, match="row window"):
            pack_nnzsplit(M, ks=8, r_cap=128)


# ---------------------------------------------------------------------------
# Execution vs the dense oracle (bit-exact, single- and multi-RHS)
# ---------------------------------------------------------------------------

class TestNnzSplitExecution:
    @pytest.mark.parametrize("nrhs", [None, 3, 8])
    def test_powerlaw_bit_identical_across_rhs_widths(self, nrhs):
        M = _unstructured(seed=3)
        _check_exact(M, ExecutionPlan(path="nnzsplit", k_step_sublanes=2),
                     nrhs=nrhs)

    def test_paper_example(self):
        _check_exact(csrc.paper_example(),
                     ExecutionPlan(path="nnzsplit", k_step_sublanes=2))

    @pytest.mark.parametrize("ks", [2, 8])
    def test_chunk_size_sweep(self, ks):
        M = _dyadic(csrc.random_symmetric_pattern(220, 5, seed=4))
        _check_exact(M, ExecutionPlan(path="nnzsplit", k_step_sublanes=ks))

    def test_int16_indices(self):
        M = _unstructured(260, seed=5)
        op = _check_exact(
            M, ExecutionPlan(path="nnzsplit", k_step_sublanes=2,
                             index_dtype="int16"))
        assert op.pack.src.dtype == jnp.int16

    def test_diag_only(self):
        n = 17
        i = np.arange(n)
        M = csrc.from_coo(i, i, np.arange(1.0, n + 1.0), n=n)
        _check_exact(M, ExecutionPlan(path="nnzsplit", k_step_sublanes=2))

    def test_n1(self):
        M = csrc.from_dense(np.array([[3.0]]))
        _check_exact(M, ExecutionPlan(path="nnzsplit"))

    def test_empty_rows(self):
        i = np.arange(0, 20, 2)
        M = csrc.from_coo(i, i, np.ones(i.size), n=20)
        _check_exact(M, ExecutionPlan(path="nnzsplit", k_step_sublanes=2))

    def test_value_refresh_zero_structural_rebuild(self):
        M = _unstructured(seed=6)
        op = ops.SpmvOperator.from_plan(
            M, ExecutionPlan(path="nnzsplit", k_step_sublanes=2))
        M2 = dataclasses.replace(M, ad=M.ad * 2, al=M.al * 2, au=M.au * 2)
        _, d = _build_delta(lambda: op.update_values(M2))
        assert d == {"value_refresh": 1}, d
        x = _dyadic_x(M.m, seed=1)
        ref = (np.asarray(csrc.to_dense(M2), np.float64)
               @ x.astype(np.float64)).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(op(jnp.asarray(x))), ref)

    def test_streamed_bytes_reported(self):
        M = _unstructured(seed=7)
        op = ops.SpmvOperator.from_plan(
            M, ExecutionPlan(path="nnzsplit", k_step_sublanes=2))
        assert isinstance(op.pack, NnzSplitPack)
        assert op.bytes_per_call == op.pack.streamed_bytes() > 0


# ---------------------------------------------------------------------------
# Schedule artifacts: cache, disk round-trip, zero-rebuild probes
# ---------------------------------------------------------------------------

class TestNnzSplitSchedule:
    def test_schedule_bundles_nnzsplit_pack_only(self):
        M = _unstructured(seed=8)
        sched = S.build_schedule(
            M, ExecutionPlan(path="nnzsplit", k_step_sublanes=2))
        assert sched.nnzsplit_pack is not None
        assert sched.pack is None and sched.flat_pack is None
        assert sched.coloring is None
        assert sched.partition.starts[-1] == M.n

    def test_cache_hit_rebuilds_zero_packs(self):
        """The acceptance probe: a second operator construction through
        the cache performs zero nnzsplit packs and is bit-identical."""
        M = _unstructured(seed=9)
        x = jnp.asarray(_dyadic_x(M.m, seed=2))
        cache = tuner.PlanCache()
        plan = ExecutionPlan(path="nnzsplit", k_step_sublanes=2)
        op1, d1 = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache))
        assert d1.get("nnzsplit_pack") == 1 and d1.get("schedule") == 1
        op2, d2 = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache))
        assert d2 == {}, f"cache hit rebuilt: {d2}"
        assert cache.schedule_hits == 1
        np.testing.assert_array_equal(np.asarray(op1(x)),
                                      np.asarray(op2(x)))

    def test_disk_roundtrip_bit_identical(self, tmp_path):
        M = _unstructured(seed=10)
        plan = ExecutionPlan(path="nnzsplit", k_step_sublanes=2)
        sched = S.build_schedule(M, plan)
        f = os.path.join(tmp_path, "nnzsplit.npz")
        sched.save_npz(f)
        loaded = S.SpmvSchedule.load_npz(f)
        assert loaded.plan == plan
        pk0, pk1 = sched.nnzsplit_pack, loaded.nnzsplit_pack
        assert (pk0.num_chunks, pk0.ks, pk0.r_pad) == \
               (pk1.num_chunks, pk1.ks, pk1.r_pad)
        x = jnp.asarray(_dyadic_x(M.m, seed=3))
        y0 = np.asarray(ops.SpmvOperator.from_plan(M, plan,
                                                   schedule=sched)(x))
        y1 = np.asarray(ops.SpmvOperator.from_plan(M, plan,
                                                   schedule=loaded)(x))
        np.testing.assert_array_equal(y0, y1)

    def test_disk_cache_hit_rebuilds_nothing(self, tmp_path):
        """Cold process simulation: a fresh PlanCache over the same file
        loads the nnzsplit schedule from npz — zero packs."""
        path = os.path.join(tmp_path, "plans.json")
        M = _unstructured(seed=11)
        plan = ExecutionPlan(path="nnzsplit", k_step_sublanes=2)
        cache1 = tuner.PlanCache(path=path)
        ops.SpmvOperator.from_plan(M, plan, cache=cache1)
        cache2 = tuner.PlanCache(path=path)       # fresh memory
        _, delta = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache2))
        assert delta == {}, f"disk hit rebuilt: {delta}"
        assert cache2.schedule_hits == 1


# ---------------------------------------------------------------------------
# Tuner end to end
# ---------------------------------------------------------------------------

def _prefer_nnzsplit(calls):
    def measure(op, x):
        calls.append(op.plan.key())
        return 1.0 if op.plan.path == "nnzsplit" else 2.0
    return measure


class TestNnzSplitTuning:
    def test_tune_selects_and_caches_nnzsplit(self):
        M = _unstructured(seed=12)
        cache = tuner.PlanCache()
        calls = []
        res = tuner.tune(M, cache=cache, measure=_prefer_nnzsplit(calls))
        assert res.plan.path == "nnzsplit"
        assert any(k.startswith("nnzsplit:") for k in res.timings_s)

        def boom(op, x):
            raise AssertionError("re-measured on a cache hit")
        res2 = tuner.tune(M, cache=cache, measure=boom)
        assert res2.cached and res2.plan == res.plan

    def test_tuned_schedule_reused_with_zero_packs(self):
        M = _unstructured(seed=13)
        cache = tuner.PlanCache()
        res = tuner.tune(M, cache=cache, measure=_prefer_nnzsplit([]))
        _, delta = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, res.plan, cache=cache))
        assert delta == {}, f"tuned-plan construction rebuilt: {delta}"

    def test_serving_engine_runs_nnzsplit_plan_bit_identical(self):
        from repro.serve.engine import SpmvServingEngine
        M = _unstructured(seed=14)
        A = np.asarray(csrc.to_dense(M), np.float64)
        cache = tuner.PlanCache()
        tuner.tune(M, cache=cache, measure=_prefer_nnzsplit([]))
        eng = SpmvServingEngine(cache=cache, autotune=True)
        plan = eng.register("unstructured", M)
        assert plan.path == "nnzsplit"
        xs = [_dyadic_x(M.m, seed=i) for i in range(4)]
        uids = [eng.submit("unstructured", x) for x in xs]
        out = eng.run_until_drained()
        assert set(out) == set(uids)
        for uid, x in zip(uids, xs):
            assert out[uid].path == "nnzsplit"
            np.testing.assert_array_equal(
                np.asarray(out[uid]),
                (A @ x.astype(np.float64)).astype(np.float32))


# ---------------------------------------------------------------------------
# Distributed: shard-local nnz-split execution (fast 1-shard mesh here;
# the 8-shard subprocess sweep lives in test_distributed_spmv.py)
# ---------------------------------------------------------------------------

class TestNnzSplitDistributedSingleShard:
    @pytest.mark.parametrize("strategy", D.STRATEGIES)
    def test_all_strategies_bit_identical_to_dense(self, strategy):
        mesh = D.make_mesh(1)
        M = _unstructured(seed=15)
        A = np.asarray(csrc.to_dense(M), np.float64)
        plan = ExecutionPlan(path="nnzsplit", k_step_sublanes=2)
        fn = D.build_sharded_spmv(M, mesh, "rows", strategy, plan=plan)
        x = _dyadic_x(M.n, seed=4)
        y = np.asarray(fn(jnp.asarray(x)))[:M.n]
        np.testing.assert_array_equal(
            y, (A @ x.astype(np.float64)).astype(np.float32))
        X = _dyadic_x(M.n, seed=5, nrhs=3)
        Y = np.asarray(fn(jnp.asarray(X)))[:M.n]
        np.testing.assert_array_equal(
            Y, (A @ X.astype(np.float64)).astype(np.float32))

    def test_shard_layouts_are_memoized(self):
        mesh = D.make_mesh(1)
        M = _unstructured(seed=16)
        plan = ExecutionPlan(path="nnzsplit", k_step_sublanes=2)
        cache = tuner.PlanCache()
        D.build_sharded_spmv(M, mesh, "rows", "allreduce", plan=plan,
                             cache=cache)
        D.build_sharded_spmv(M, mesh, "rows", "halo", plan=plan,
                             cache=cache)
        _, delta = _build_delta(lambda: (
            D.build_sharded_spmv(M, mesh, "rows", "allreduce", plan=plan,
                                 cache=cache),
            D.build_sharded_spmv(M, mesh, "rows", "halo", plan=plan,
                                 cache=cache)))
        assert delta == {}, f"repeated build re-ran precompute: {delta}"

    @pytest.mark.parametrize("acc", ["allreduce", "reduce_scatter", "halo"])
    def test_mesh_executor_bit_identical_to_local_p1(self, acc):
        from repro.serve import LocalExecutor, MeshExecutor
        M = _unstructured(seed=17)
        lplan = ExecutionPlan(path="nnzsplit", k_step_sublanes=2)
        local = LocalExecutor(M, lplan)
        mesh = MeshExecutor(M, dataclasses.replace(
            lplan, strategy="mesh", mesh_p=1, accumulation=acc))
        for nrhs in (None, 3, 8):
            x = jnp.asarray(_dyadic_x(M.m, seed=nrhs or 1, nrhs=nrhs))
            np.testing.assert_array_equal(np.asarray(local(x)),
                                          np.asarray(mesh(x)))

    @pytest.mark.parametrize("acc", ["allreduce", "halo"])
    def test_mesh_value_refresh_p1(self, acc):
        from repro.serve import MeshExecutor
        M = _unstructured(seed=18)
        ex = MeshExecutor(M, ExecutionPlan(
            path="nnzsplit", k_step_sublanes=2, strategy="mesh", mesh_p=1,
            accumulation=acc))
        M2 = dataclasses.replace(M, ad=M.ad * 2, al=M.al * 2, au=M.au * 2)
        _, d = _build_delta(lambda: ex.update_values(M2))
        assert d.get("shard_value_refresh") == 1, d
        assert not any(d.get(k) for k in STRUCTURAL_KEYS), d
        x = _dyadic_x(M.m, seed=6)
        np.testing.assert_array_equal(
            np.asarray(ex(jnp.asarray(x))),
            (np.asarray(csrc.to_dense(M2), np.float64)
             @ x.astype(np.float64)).astype(np.float32))


# ---------------------------------------------------------------------------
# from_scipy quickstart path
# ---------------------------------------------------------------------------

class TestFromScipy:
    def test_from_scipy_roundtrip(self):
        sp = pytest.importorskip("scipy.sparse")
        rng = np.random.default_rng(0)
        A = sp.random(60, 60, density=0.08, random_state=0,
                      data_rvs=lambda k: rng.integers(-8, 8, k) / 4.0)
        A = (A + A.T).tocsr()                    # structurally symmetric
        A.setdiag(np.arange(1.0, 61.0))
        M = csrc.CSRC.from_scipy(A)
        np.testing.assert_array_equal(np.asarray(csrc.to_dense(M)),
                                      A.toarray().astype(np.float32))
        x = _dyadic_x(60, seed=7)
        y = np.asarray(ops.SpmvOperator.from_plan(
            M, ExecutionPlan(path="nnzsplit", k_step_sublanes=2))(
                jnp.asarray(x)))
        ref = (A.toarray().astype(np.float64)
               @ x.astype(np.float64)).astype(np.float32)
        np.testing.assert_array_equal(y, ref)
