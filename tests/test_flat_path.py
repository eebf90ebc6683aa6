"""The flat-grid kernel as a first-class KernelPath: registry dispatch,
tuner enumeration (skew-gated, feasibility-filtered), schedule artifacts
with cache/disk round-trips and zero-rebuild probes, multi-RHS execution
vs the dense oracle, shard-local flat execution in every distributed
strategy, and the serving engine running a tuned flat plan."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _propshim import given, settings, st
from repro.core import csrc, distributed as D, paths, schedule as S, tuner
from repro.core.plan import PATHS, ExecutionPlan, feasible
from repro.kernels import ops
from repro.kernels.csrc_spmv_flat import flat_spmm, flat_spmv, pack_flat


def _skewed(n=256, wide=48, narrow=3, seed=1, **kw):
    return csrc.skewed_band(n, wide, narrow, seed=seed, **kw)


def _check_against_dense(M, plan, nrhs=1, rtol=2e-4, seed=11):
    A = csrc.to_dense(M).astype(np.float64)
    rng = np.random.default_rng(seed)
    shape = (M.m,) if nrhs == 1 else (M.m, nrhs)
    x = rng.standard_normal(shape).astype(np.float32)
    y_ref = A @ x.astype(np.float64)
    scale = max(1.0, np.abs(y_ref).max())
    op = ops.SpmvOperator.from_plan(M, plan)
    assert op.plan.path == plan.path          # strict: no silent fallback
    y = np.asarray(op(jnp.asarray(x)), dtype=np.float64)
    np.testing.assert_allclose(y / scale, y_ref / scale, rtol=rtol,
                               atol=rtol, err_msg=f"plan {plan.key()}")
    return op


# ---------------------------------------------------------------------------
# Registry + plan layer
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_flat_is_a_registered_path(self):
        assert "flat" in PATHS
        entry = paths.get_path("flat")
        assert entry.name == "flat"
        plan = ExecutionPlan(path="flat", tm=64)
        assert plan.key().startswith("flat:tm64:")

    def test_every_builtin_path_is_registered(self):
        names = {e.name for e in paths.registered_paths()}
        assert {"segment", "kernel", "colorful", "flat"} <= names
        # the registry is the source of truth for plan validation
        assert set(PATHS) == names

    def test_unknown_path_rejected(self):
        with pytest.raises(KeyError):
            paths.get_path("warp")
        with pytest.raises(ValueError):
            ExecutionPlan(path="warp")

    def test_flat_feasibility_mirrors_kernel_gate(self):
        M = _skewed(128, 16)
        band = csrc.bandwidth(M)
        ok = ExecutionPlan(path="flat", tm=32)
        assert feasible(ok, n=M.n, m=M.m, bandwidth=band)
        tight = ExecutionPlan(path="flat", tm=128, w_cap=64)
        assert not feasible(tight, n=M.n, m=M.m, bandwidth=band)
        # square-only
        assert not feasible(ok, n=64, m=96, bandwidth=band)


class TestEnumeration:
    def test_flat_emitted_on_skewed_matrices(self):
        M = _skewed()
        stats = tuner.stats_of(M)
        assert paths.flat_worth_measuring(stats), "not skewed?"
        plans = tuner.enumerate_plans(stats, tms=(32, 64))
        flat = [p for p in plans if p.path == "flat"]
        assert flat, [p.key() for p in plans]
        for p in flat:
            assert feasible(p, n=M.n, m=M.m, bandwidth=stats.bandwidth)

    def test_flat_skipped_on_uniform_rows(self):
        """Uniform nnz-per-row: the rectangular grid pads nothing, so a
        flat candidate is not worth measuring."""
        M = csrc.fem_band(128, 2, seed=0, fill=1.0)
        stats = tuner.stats_of(M)
        assert not paths.flat_worth_measuring(stats)
        plans = tuner.enumerate_plans(stats)
        assert not any(p.path == "flat" for p in plans)

    def test_unpackable_matrices_reject_flat_and_kernel(self):
        """The bugfix: a matrix the packer cannot tile (bandwidth ~ n,
        window over w_cap) must yield no 'flat'/'kernel' candidates
        instead of erroring mid-tune."""
        M = csrc.random_symmetric_pattern(300, 4, seed=0)
        stats = tuner.stats_of(M)
        plans = tuner.enumerate_plans(stats, w_cap=256)
        assert plans                       # segment survives
        assert not any(p.path in ("flat", "kernel") for p in plans)
        # ... and tuning such a matrix completes on the surviving paths
        res = tuner.tune(M, cache=tuner.PlanCache(),
                         measure=lambda op, x: 1.0)
        assert res.plan.path not in ("flat", "kernel")

    def test_candidate_source_plans_are_feasibility_filtered(self):
        """Plans injected through the legacy hook get the same feasibility
        gate as registry candidates — an unpackable flat plan never
        reaches measurement."""
        bad = ExecutionPlan(path="flat", tm=128, w_cap=128)
        ok = ExecutionPlan(path="segment", w_cap=777)

        def source(stats):
            return [bad, ok]

        tuner.register_candidate_source(source)
        try:
            M = csrc.random_symmetric_pattern(300, 4, seed=1)
            plans = tuner.enumerate_plans(tuner.stats_of(M))
            assert ok in plans
            assert bad not in plans
        finally:
            tuner._CANDIDATE_SOURCES.remove(source)

    def test_rectangular_matrix_yields_no_flat(self):
        M = csrc.rectangular_fem(48, 16, 4, seed=5)
        plans = tuner.enumerate_plans(tuner.stats_of(M))
        assert all(p.path == "segment" for p in plans)
        with pytest.raises(ValueError):
            ops.SpmvOperator.from_plan(M, ExecutionPlan(path="flat"))


# ---------------------------------------------------------------------------
# Execution vs the dense oracle (single- and multi-RHS, edge cases)
# ---------------------------------------------------------------------------

class TestFlatExecution:
    @pytest.mark.parametrize("nrhs", [1, 3, 8])
    def test_matches_dense_across_rhs_widths(self, nrhs):
        M = _skewed()
        _check_against_dense(M, ExecutionPlan(path="flat", tm=64),
                             nrhs=nrhs)

    @pytest.mark.parametrize("nrhs", [1, 3])
    def test_numerically_symmetric_stream(self, nrhs):
        M = _skewed(seed=7, numeric_symmetric=True)
        op = _check_against_dense(
            M, ExecutionPlan(path="flat", tm=32), nrhs=nrhs)
        assert op.schedule.flat_pack.num_symmetric

    def test_rectangular_tail_tile(self):
        """n not a multiple of tm: the last tile is partial."""
        M = csrc.fem_band(130, 5, seed=3)
        assert 130 % 64 != 0
        _check_against_dense(M, ExecutionPlan(path="flat", tm=64))

    def test_empty_rows(self):
        i = np.arange(0, 20, 2)
        M = csrc.from_coo(i, i, np.ones(i.size), n=20)
        _check_against_dense(M, ExecutionPlan(path="flat", tm=8))

    def test_n1(self):
        M = csrc.from_dense(np.array([[3.0]]))
        _check_against_dense(M, ExecutionPlan(path="flat"))

    def test_diag_only(self):
        n = 17
        i = np.arange(n)
        M = csrc.from_coo(i, i, np.arange(1.0, n + 1.0), n=n)
        _check_against_dense(M, ExecutionPlan(path="flat", tm=8))

    def test_flat_beats_rect_padding_and_bytes_on_skew(self):
        """The reason 'flat' exists: on a skewed matrix its pad_ratio and
        streamed_bytes are strictly below the rectangular grid's."""
        M = _skewed(1024, 48, 3, seed=1)
        rect = ops.SpmvOperator.from_plan(
            M, ExecutionPlan(path="kernel", tm=64))
        flat = ops.SpmvOperator.from_plan(
            M, ExecutionPlan(path="flat", tm=64))
        assert flat.pack.pad_ratio < rect.pack.pad_ratio
        assert flat.bytes_per_call < rect.bytes_per_call

    @settings(max_examples=4, deadline=None)
    @given(st.integers(16, 100), st.integers(1, 10), st.integers(0, 10_000),
           st.booleans())
    def test_property_flat_matches_dense(self, n, band, seed, sym):
        M = csrc.fem_band(n, min(band, n - 1), seed=seed,
                          numeric_symmetric=sym)
        _check_against_dense(M, ExecutionPlan(path="flat", tm=8))

    @settings(max_examples=3, deadline=None)
    @given(st.integers(16, 80), st.integers(1, 8), st.integers(0, 10_000),
           st.sampled_from([3, 8]))
    def test_property_flat_spmm_matches_dense(self, n, band, seed, nrhs):
        M = csrc.fem_band(n, min(band, n - 1), seed=seed)
        _check_against_dense(M, ExecutionPlan(path="flat", tm=8),
                             nrhs=nrhs)


# ---------------------------------------------------------------------------
# Schedule artifacts: cache, disk round-trip, zero-rebuild probes
# ---------------------------------------------------------------------------

def _build_delta(fn):
    before = dict(S.BUILD_COUNTS)
    out = fn()
    after = dict(S.BUILD_COUNTS)
    return out, {k: after.get(k, 0) - before.get(k, 0)
                 for k in set(after) | set(before)
                 if after.get(k, 0) != before.get(k, 0)}


class TestFlatSchedule:
    def test_schedule_bundles_flat_pack_only(self):
        M = _skewed(128, 16)
        sched = S.build_schedule(M, ExecutionPlan(path="flat", tm=32))
        assert sched.flat_pack is not None
        assert sched.pack is None and sched.coloring is None
        assert sched.partition.starts[-1] == M.n

    def test_cache_hit_rebuilds_zero_flat_packs(self):
        """The acceptance probe: a second operator construction through
        the cache performs zero flat packs and is bit-identical."""
        M = _skewed(96, 12)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(M.m)
                        .astype(np.float32))
        cache = tuner.PlanCache()
        plan = ExecutionPlan(path="flat", tm=32)
        op1, d1 = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache))
        assert d1.get("flat_pack") == 1 and d1.get("schedule") == 1
        op2, d2 = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache))
        assert d2 == {}, f"cache hit rebuilt: {d2}"
        assert cache.schedule_hits == 1
        np.testing.assert_array_equal(np.asarray(op1(x)),
                                      np.asarray(op2(x)))

    def test_disk_roundtrip_bit_identical(self, tmp_path):
        M = _skewed(96, 12, seed=4)
        plan = ExecutionPlan(path="flat", tm=32)
        sched = S.build_schedule(M, plan)
        f = os.path.join(tmp_path, "flat.npz")
        sched.save_npz(f)
        loaded = S.SpmvSchedule.load_npz(f)
        assert loaded.plan == plan
        pk0, pk1 = sched.flat_pack, loaded.flat_pack
        assert (pk0.total_steps, pk0.w_pad, pk0.nt) == \
               (pk1.total_steps, pk1.w_pad, pk1.nt)
        x = jnp.asarray(np.random.default_rng(1).standard_normal(M.m)
                        .astype(np.float32))
        y0 = np.asarray(ops.SpmvOperator.from_plan(M, plan,
                                                   schedule=sched)(x))
        y1 = np.asarray(ops.SpmvOperator.from_plan(M, plan,
                                                   schedule=loaded)(x))
        np.testing.assert_array_equal(y0, y1)

    def test_disk_cache_hit_rebuilds_nothing(self, tmp_path):
        """Cold process simulation: a fresh PlanCache over the same file
        loads the flat schedule from npz — zero flat packs."""
        path = os.path.join(tmp_path, "plans.json")
        M = _skewed(96, 12, seed=6)
        plan = ExecutionPlan(path="flat", tm=32)
        cache1 = tuner.PlanCache(path=path)
        ops.SpmvOperator.from_plan(M, plan, cache=cache1)
        cache2 = tuner.PlanCache(path=path)       # fresh memory
        _, delta = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache2))
        assert delta == {}, f"disk hit rebuilt: {delta}"
        assert cache2.schedule_hits == 1

    def test_version_bump_invalidates(self, tmp_path, monkeypatch):
        M = _skewed(64, 8, seed=8)
        plan = ExecutionPlan(path="flat", tm=32)
        sched = S.build_schedule(M, plan)
        f = os.path.join(tmp_path, "flat.npz")
        sched.save_npz(f)
        monkeypatch.setattr(S, "SCHEDULE_VERSION", S.SCHEDULE_VERSION + 1)
        with pytest.raises(ValueError):
            S.SpmvSchedule.load_npz(f)

    def test_artifact_shared_across_accumulation_and_nrhs(self):
        a = ExecutionPlan(path="flat", tm=32, accumulation="halo")
        b = ExecutionPlan(path="flat", tm=32,
                          accumulation="reduce_scatter", nrhs=8)
        c = ExecutionPlan(path="flat", tm=64, accumulation="halo")
        assert S.plan_artifact_fields(a) == S.plan_artifact_fields(b)
        assert S.plan_artifact_fields(a) != S.plan_artifact_fields(c)


# ---------------------------------------------------------------------------
# Tuner end to end
# ---------------------------------------------------------------------------

def _prefer_flat(calls):
    def measure(op, x):
        calls.append(op.plan.key())
        return 1.0 if op.plan.path == "flat" else 2.0
    return measure


class TestFlatTuning:
    def test_tune_selects_and_caches_flat(self):
        M = _skewed()
        cache = tuner.PlanCache()
        calls = []
        res = tuner.tune(M, cache=cache, measure=_prefer_flat(calls))
        assert res.plan.path == "flat"
        assert any(k.startswith("flat:") for k in res.timings_s)

        def boom(op, x):
            raise AssertionError("re-measured on a cache hit")
        res2 = tuner.tune(M, cache=cache, measure=boom)
        assert res2.cached and res2.plan == res.plan

    def test_tuned_schedule_reused_with_zero_packs(self):
        """tune() stores the winner's schedule next to the plan: operator
        construction afterwards rebuilds nothing."""
        M = _skewed(seed=9)
        cache = tuner.PlanCache()
        res = tuner.tune(M, cache=cache, measure=_prefer_flat([]))
        _, delta = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, res.plan, cache=cache))
        assert delta == {}, f"tuned-plan construction rebuilt: {delta}"

    def test_serving_engine_runs_flat_plan(self):
        from repro.serve.engine import SpmvServingEngine
        M = _skewed(seed=10)
        A = csrc.to_dense(M)
        cache = tuner.PlanCache()
        tuner.tune(M, cache=cache, measure=_prefer_flat([]))
        eng = SpmvServingEngine(cache=cache, autotune=True)
        plan = eng.register("skew", M)
        assert plan.path == "flat"
        rng = np.random.default_rng(3)
        xs = [rng.standard_normal(M.m).astype(np.float32)
              for _ in range(4)]
        uids = [eng.submit("skew", x) for x in xs]
        out = eng.run_until_drained()
        assert set(out) == set(uids)
        for uid, x in zip(uids, xs):
            np.testing.assert_allclose(out[uid], A @ x, rtol=2e-4,
                                       atol=2e-4)


# ---------------------------------------------------------------------------
# Distributed: shard-local flat execution (fast 1-shard mesh here; the
# 8-shard subprocess sweep lives in test_distributed_spmv.py)
# ---------------------------------------------------------------------------

class TestFlatDistributedSingleShard:
    @pytest.mark.parametrize("strategy", D.STRATEGIES)
    def test_all_strategies_match_dense(self, strategy):
        mesh = D.make_mesh(1)
        M = _skewed(192, 24, seed=2)
        A = csrc.to_dense(M)
        plan = ExecutionPlan(path="flat", tm=32)
        fn = D.build_sharded_spmv(M, mesh, "rows", strategy, plan=plan)
        rng = np.random.default_rng(0)
        x = rng.standard_normal(M.n).astype(np.float32)
        y = np.asarray(fn(jnp.asarray(x)))[:M.n]
        ref = A @ x
        np.testing.assert_allclose(y, ref, rtol=2e-4,
                                   atol=2e-4 * max(1, np.abs(ref).max()))
        X = rng.standard_normal((M.n, 3)).astype(np.float32)
        Y = np.asarray(fn(jnp.asarray(X)))[:M.n]
        refm = A @ X
        np.testing.assert_allclose(Y, refm, rtol=2e-4,
                                   atol=2e-4 * max(1, np.abs(refm).max()))

    @pytest.mark.parametrize("strategy", D.STRATEGIES)
    def test_stream_plan_runs_the_stream_form_per_shard(self, strategy):
        """A stream plan runs the fused XLA form shard-locally: no Pallas
        call in the program, the same product as the one-hot kernel."""
        mesh = D.make_mesh(1)
        M = _skewed(192, 24, seed=2)
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            M.n).astype(np.float32))
        ys = {}
        for variant in ("stream", "onehot"):
            fn = D.build_sharded_spmv(
                M, mesh, "rows", strategy,
                plan=ExecutionPlan(path="flat", tm=32, variant=variant))
            jaxpr = str(jax.make_jaxpr(fn)(x))
            assert ("pallas_call" in jaxpr) == (variant == "onehot")
            ys[variant] = np.asarray(fn(x))[:M.n]
        np.testing.assert_allclose(ys["stream"], ys["onehot"], rtol=1e-5,
                                   atol=1e-5 * np.abs(ys["onehot"]).max())

    def test_shard_layouts_are_memoized(self):
        """Repeated builder calls (serving restarts) are zero-precompute:
        the schedule comes from the cache, the per-shard flat layouts
        from their memos."""
        mesh = D.make_mesh(1)
        M = _skewed(160, 16, seed=3)
        plan = ExecutionPlan(path="flat", tm=32)
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
