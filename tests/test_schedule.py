"""The unified schedule layer (core/schedule.py): cache round-trips with
zero re-pack/re-color, bit-identical execution from deserialized artifacts,
balanced largest-degree-first coloring invariants, and multi-RHS SpMM vs
the dense oracle across all three paths."""
import dataclasses
import os

import numpy as np
import jax.numpy as jnp
import pytest

from _propshim import given, settings, st
from repro.core import csrc, schedule as S, tuner
from repro.core.coloring import balance_stats, color_rows, verify_coloring
from repro.core.plan import ExecutionPlan
from repro.kernels import ops


def _build_delta(fn):
    """Run fn and return (result, builds-that-happened) from the probe."""
    before = dict(S.BUILD_COUNTS)
    out = fn()
    after = dict(S.BUILD_COUNTS)
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in set(after) | set(before)}
    return out, {k: v for k, v in delta.items() if v}


# ---------------------------------------------------------------------------
# Schedule build + cache behavior
# ---------------------------------------------------------------------------

def test_schedule_bundles_everything_per_path():
    M = csrc.fem_band(72, 5, seed=1)
    kernel = S.build_schedule(M, ExecutionPlan(path="kernel", tm=8))
    assert kernel.pack is not None and kernel.coloring is None
    colorful = S.build_schedule(M, ExecutionPlan(path="colorful"))
    assert colorful.pack is None and colorful.coloring is not None
    assert colorful.color_slots.shape[0] == M.k
    segment = S.build_schedule(M, ExecutionPlan(path="segment"))
    assert segment.pack is None and segment.coloring is None
    for sched in (kernel, colorful, segment):
        assert sched.partition.starts[-1] == M.n
        assert sched.halo.shape == (sched.partition.p,)


def test_schedule_strictness_matches_plan_gates():
    Mr = csrc.rectangular_fem(32, 8, 3, seed=0)
    with pytest.raises(ValueError):
        S.build_schedule(Mr, ExecutionPlan(path="kernel"))
    with pytest.raises(ValueError):
        S.build_schedule(Mr, ExecutionPlan(path="colorful"))
    Mu = csrc.random_symmetric_pattern(300, 4, seed=0)   # bandwidth ~ n
    with pytest.raises(ValueError):
        S.build_schedule(Mu, ExecutionPlan(path="kernel", w_cap=256))


def test_cache_hit_skips_all_precompute():
    """The acceptance probe: a second operator construction for the same
    (matrix, plan) through the cache performs zero pack/partition/coloring
    work, and produces bit-identical results."""
    M = csrc.fem_band(48, 4, seed=3)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(M.m)
                    .astype(np.float32))
    cache = tuner.PlanCache()
    plan = ExecutionPlan(path="kernel", tm=8)
    op1, d1 = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache))
    assert d1.get("pack") == 1 and d1.get("schedule") == 1
    op2, d2 = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache))
    assert d2 == {}, f"cache hit rebuilt: {d2}"
    assert cache.schedule_hits == 1
    np.testing.assert_array_equal(np.asarray(op1(x)), np.asarray(op2(x)))


def test_same_class_different_values_does_not_share_schedule():
    """fingerprint() keys a matrix *class*; the schedule embeds values, so
    a same-class matrix with different values must never silently reuse
    another matrix's value streams.  With an identical *structure* the
    schedule layer satisfies that via the value-refresh fast path (new
    streams, zero structural rebuild) instead of a full re-pack."""
    M1 = csrc.fem_band(64, 3, seed=7)
    M2 = csrc.from_dense(2.0 * csrc.to_dense(M1))       # same structure
    assert tuner.fingerprint(M1) == tuner.fingerprint(M2)
    assert S.value_digest(M1) != S.value_digest(M2)
    cache = tuner.PlanCache()
    plan = ExecutionPlan(path="kernel", tm=8)
    op1 = ops.SpmvOperator.from_plan(M1, plan, cache=cache)
    op2, d = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M2, plan, cache=cache))
    # M2's own value streams were installed (no silent reuse of M1's) ...
    assert d == {"value_refresh": 1}
    # ... and the results really are M2's, i.e. 2x M1's
    x = jnp.asarray(np.random.default_rng(1).standard_normal(M1.m)
                    .astype(np.float32))
    np.testing.assert_allclose(np.asarray(op2(x)),
                               2.0 * np.asarray(op1(x)),
                               rtol=1e-6, atol=1e-6)


def test_schedule_npz_roundtrip_through_disk_cache(tmp_path):
    """Round-trip the artifact through a disk-backed PlanCache: a fresh
    process (new cache object) loads the npz and re-packs nothing; SpMV and
    SpMM results are bit-identical to the originally-built operator."""
    path = os.path.join(tmp_path, "plans.json")
    M = csrc.fem_band(48, 3, seed=1)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(M.m)
                    .astype(np.float32))
    X = jnp.asarray(np.random.default_rng(3).standard_normal((M.m, 2))
                    .astype(np.float32))
    for plan in (ExecutionPlan(path="kernel", tm=8),
                 ExecutionPlan(path="colorful"),
                 ExecutionPlan(path="segment")):
        cache = tuner.PlanCache(path=path)
        op1 = ops.SpmvOperator.from_plan(M, plan, cache=cache)
        cache2 = tuner.PlanCache(path=path)          # "new process"
        op2, d = _build_delta(
            lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache2))
        assert d == {}, f"{plan.path}: disk hit rebuilt {d}"
        np.testing.assert_array_equal(np.asarray(op1(X)),
                                      np.asarray(op2(X)))
        if plan.path == "kernel":       # 1-D path bit-identical too
            np.testing.assert_array_equal(np.asarray(op1(x)),
                                          np.asarray(op2(x)))


def test_schedule_version_mismatch_invalidates(tmp_path, monkeypatch):
    """Bumping SCHEDULE_VERSION (a format change) silently invalidates
    stored schedules: the next request rebuilds instead of crashing."""
    path = os.path.join(tmp_path, "plans.json")
    M = csrc.fem_band(48, 3, seed=9)
    plan = ExecutionPlan(path="kernel", tm=8)
    cache = tuner.PlanCache(path=path)
    ops.SpmvOperator.from_plan(M, plan, cache=cache)
    monkeypatch.setattr(S, "SCHEDULE_VERSION", S.SCHEDULE_VERSION + 1)
    cache2 = tuner.PlanCache(path=path)
    _, d = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache2))
    assert d.get("pack") == 1        # rebuilt under the new version


def test_tune_stores_winning_schedule():
    M = csrc.poisson2d(8)
    cache = tuner.PlanCache()
    res = tuner.tune(M, cache=cache,
                     measure=lambda op, x: 1.0 if op.plan.path == "kernel"
                     else 2.0)
    assert len(cache.schedules) == 1
    _, d = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M, res.plan, cache=cache))
    assert d == {} and cache.schedule_hits == 1


# ---------------------------------------------------------------------------
# Coloring quality: largest-degree-first + RACE-style balancing
# ---------------------------------------------------------------------------

# Small-scale analogs of the generator matrix classes — the invariant set
# for coloring quality.
COLORING_SET = [
    ("poisson", lambda: csrc.poisson2d(8)),
    ("narrow_band1", lambda: csrc.fem_band(120, 1, seed=1)),
    ("fem_band_w4", lambda: csrc.fem_band(120, 4, seed=2)),
    ("fem_band_w8", lambda: csrc.fem_band(80, 8, seed=3)),
    ("fem_band_w8_sym", lambda: csrc.fem_band(80, 8, seed=3,
                                              numeric_symmetric=True)),
    ("random_nnz4", lambda: csrc.random_symmetric_pattern(80, 4, seed=4)),
    ("dense", lambda: csrc.dense_matrix(24, seed=5)),
]


@pytest.mark.parametrize("name,make", COLORING_SET,
                         ids=[n for n, _ in COLORING_SET])
def test_degree_ordering_never_beaten_by_unordered(name, make):
    """Satellite invariant: the default (largest-degree-first) colorer never
    uses more colors than the legacy unordered greedy, on every benchmark
    matrix class."""
    M = make()
    legacy = color_rows(M, order="natural", balance=False)
    tuned = color_rows(M)
    assert tuned.num_colors <= legacy.num_colors
    assert verify_coloring(M, tuned)


@pytest.mark.parametrize("name,make", COLORING_SET[:5],
                         ids=[n for n, _ in COLORING_SET[:5]])
def test_balancing_reduces_dispersion_preserves_colors(name, make):
    M = make()
    raw = color_rows(M, balance=False)
    bal = color_rows(M, balance=True)
    assert bal.num_colors <= raw.num_colors
    assert verify_coloring(M, bal)
    assert balance_stats(bal)["std"] <= balance_stats(raw)["std"] + 1e-9


def test_balanced_color_classes_keep_row_locality():
    """Rows inside one color class are emitted in ascending row order (the
    §3.2 locality criticism: iteration inside a color should stride
    monotonically through y)."""
    M = csrc.fem_band(120, 4, seed=6)
    col = color_rows(M)
    for c in range(col.num_colors):
        rows = col.rows(c)
        assert (np.diff(rows) > 0).all()


@settings(max_examples=6, deadline=None)
@given(st.integers(8, 48), st.integers(1, 5), st.integers(0, 1000))
def test_property_balanced_coloring_conflict_free(n, band, seed):
    M = csrc.fem_band(n, min(band, n - 1), seed=seed)
    col = color_rows(M)
    assert verify_coloring(M, col)
    covered = sorted(np.concatenate(
        [col.rows(c) for c in range(col.num_colors)]).tolist())
    assert covered == list(range(n))


# ---------------------------------------------------------------------------
# Multi-RHS SpMM vs the dense oracle (all paths, edge-case matrices)
# ---------------------------------------------------------------------------

def _empty_rows(n):
    i = np.arange(0, n, 2)
    return csrc.from_coo(i, i, np.ones(i.size), n=n)


SPMM_CASES = [
    ("fem_band", lambda: csrc.fem_band(48, 4, seed=1)),
    ("poisson", lambda: csrc.poisson2d(7)),
    ("rect_tail", lambda: csrc.rectangular_fem(40, 12, 3, seed=5)),
    ("empty_rows", lambda: _empty_rows(20)),
]


@pytest.mark.parametrize("nrhs", [1, 3, 8])
@pytest.mark.parametrize("name,make", SPMM_CASES,
                         ids=[n for n, _ in SPMM_CASES])
def test_spmm_matches_dense_oracle_all_plans(name, make, nrhs):
    """Acceptance: batched SpMM results match the dense oracle for
    nrhs in {1, 3, 8} on every feasible path (kernel, segment, colorful),
    including the rectangular tail and empty-row matrices."""
    M = make()
    A = csrc.to_dense(M).astype(np.float64)
    X = np.random.default_rng(nrhs).standard_normal(
        (M.m, nrhs)).astype(np.float32)
    Y_ref = A @ X.astype(np.float64)
    scale = max(1.0, np.abs(Y_ref).max())
    plans = tuner.enumerate_plans(tuner.stats_of(M), tms=(8,),
                                  nrhs_options=(nrhs,))
    assert plans
    for plan in plans:
        op = ops.SpmvOperator.from_plan(M, plan)
        Y = np.asarray(op(jnp.asarray(X)), dtype=np.float64)
        np.testing.assert_allclose(Y / scale, Y_ref / scale,
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"plan {plan.key()}")
        if nrhs == 1 and name == "fem_band":
            y1 = np.asarray(op(jnp.asarray(X[:, 0])), dtype=np.float64)
            np.testing.assert_allclose(y1, Y[:, 0], rtol=1e-6, atol=1e-6)


@settings(max_examples=3, deadline=None)
@given(st.integers(10, 32), st.integers(1, 4), st.integers(0, 10_000),
       st.sampled_from([1, 3, 8]))
def test_property_spmm_random_band(n, band, seed, nrhs):
    M = csrc.fem_band(n, min(band, max(1, n - 1)), seed=seed)
    A = csrc.to_dense(M).astype(np.float64)
    X = np.random.default_rng(seed).standard_normal(
        (M.m, nrhs)).astype(np.float32)
    Y_ref = A @ X.astype(np.float64)
    scale = max(1.0, np.abs(Y_ref).max())
    for plan in tuner.enumerate_plans(tuner.stats_of(M), tms=(8,)):
        Y = np.asarray(ops.SpmvOperator.from_plan(M, plan)(jnp.asarray(X)),
                       dtype=np.float64)
        np.testing.assert_allclose(Y / scale, Y_ref / scale,
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"plan {plan.key()}")


def test_plan_nrhs_field_and_key():
    p = ExecutionPlan(path="segment", nrhs=8)
    assert p.key().endswith(":r8")
    assert ExecutionPlan.from_json(p.to_json()) == p
    with pytest.raises(ValueError):
        ExecutionPlan(nrhs=0)
    # old cache entries (no nrhs key) deserialize to nrhs=1
    d = p.to_dict()
    del d["nrhs"]
    assert ExecutionPlan.from_dict(d).nrhs == 1


def test_enumerate_plans_nrhs_options():
    stats = tuner.stats_of(csrc.poisson2d(6))
    plans = tuner.enumerate_plans(stats, nrhs_options=(1, 4))
    widths = {p.nrhs for p in plans}
    assert widths == {1, 4}
    base = tuner.enumerate_plans(stats)
    assert len(plans) == 2 * len(base)


# ---------------------------------------------------------------------------
# Serving engine: coalesced SpMM + zero-build registration
# ---------------------------------------------------------------------------

def test_serving_register_cache_hit_zero_builds():
    from repro.serve.engine import SpmvServingEngine
    M = csrc.fem_band(80, 4, seed=2)
    cache = tuner.PlanCache()
    tuner.tune(M, cache=cache,
               measure=lambda op, x: 1.0 if op.plan.path == "kernel" else 2.0)
    eng = SpmvServingEngine(cache=cache, autotune=True)
    _, d = _build_delta(lambda: eng.register("fem", M))
    assert d == {}, f"cache-hit register did precompute work: {d}"


def test_serving_step_coalesces_into_one_spmm():
    """All pending requests for one matrix are answered by a single batched
    operator call (probe: count operator invocations)."""
    from repro.serve.engine import SpmvServingEngine
    M = csrc.fem_band(64, 3, seed=4)
    A = csrc.to_dense(M)
    eng = SpmvServingEngine()
    eng.register("m", M)
    op = eng._ops["m"]
    calls = []
    orig = op.__call__

    class CountingOp:
        plan = op.plan
        path = op.path

        def __call__(self, x):
            calls.append(getattr(x, "ndim", 1))
            return orig(x)

    eng._ops["m"] = CountingOp()
    rng = np.random.default_rng(5)
    xs = [rng.standard_normal(M.m).astype(np.float32) for _ in range(5)]
    uids = [eng.submit("m", x) for x in xs]
    out = eng.step()
    assert set(out) == set(uids)
    assert calls == [2], f"expected one batched SpMM call, got {calls}"
    for uid, x in zip(uids, xs):
        np.testing.assert_allclose(out[uid], A @ x, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Value-refresh fast path (same structure, new values — FEM time stepping)
# ---------------------------------------------------------------------------

def _same_structure_scaled(M, factor=1.5, shift=0.25):
    """A matrix with identical structure but different values."""
    A = csrc.to_dense(M)
    return csrc.from_dense(np.where(A != 0, A * factor + shift, 0.0))


@pytest.mark.parametrize("path,tm", [("kernel", 8), ("flat", 8),
                                     ("colorful", 8), ("segment", 8)])
def test_schedule_value_refresh_skips_structural_rebuild(path, tm):
    """On a value-digest miss with a same-structure schedule cached, the
    schedule layer refreshes value streams only: exactly one value_refresh,
    no pack/partition/coloring/schedule build — on every path."""
    M1 = csrc.skewed_band(96, 12, 3, seed=2)
    M2 = _same_structure_scaled(M1)
    assert S.structure_digest(M1) == S.structure_digest(M2)
    assert S.value_digest(M1) != S.value_digest(M2)
    cache = tuner.PlanCache()
    plan = ExecutionPlan(path=path, tm=tm)
    ops.SpmvOperator.from_plan(M1, plan, cache=cache)
    op2, d = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M2, plan, cache=cache))
    assert d == {"value_refresh": 1}, f"{path}: structural rebuild {d}"
    x = jnp.asarray(np.random.default_rng(3).standard_normal(M2.m)
                    .astype(np.float32))
    ref = csrc.to_dense(M2).astype(np.float64) @ np.asarray(x, np.float64)
    scale = max(1.0, np.abs(ref).max())
    np.testing.assert_allclose(
        np.asarray(op2(x), np.float64) / scale, ref / scale,
        rtol=2e-4, atol=2e-4, err_msg=f"path {path}")


def test_value_refresh_replaces_superseded_generation(tmp_path):
    """Time stepping through the cache keeps ONE schedule per structure in
    memory (each refresh evicts the generation it superseded) and does NOT
    re-compress an npz per step — the structural generation written at
    build time keeps serving fresh processes."""
    path = os.path.join(tmp_path, "plans.json")
    cache = tuner.PlanCache(path=path)
    plan = ExecutionPlan(path="kernel", tm=8)
    M = csrc.fem_band(64, 4, seed=0)
    ops.SpmvOperator.from_plan(M, plan, cache=cache)
    for t in range(4):
        M = _same_structure_scaled(M, factor=1.0, shift=0.5)
        ops.SpmvOperator.from_plan(M, plan, cache=cache)
    assert len(cache.schedules) == 1
    files = [f for f in os.listdir(cache._schedule_dir())
             if f.endswith(".npz")]
    assert len(files) == 1
    # and the surviving generation is the newest one
    sched = next(iter(cache.schedules.values()))
    assert sched.value_digest == S.value_digest(M)


def test_operator_update_values_in_place():
    """SpmvOperator.update_values: refresh the live operator; results match
    a freshly built operator bit-for-bit, with zero structural work."""
    M1 = csrc.fem_band(64, 4, seed=5)
    M2 = _same_structure_scaled(M1)
    op = ops.SpmvOperator.from_plan(M1, ExecutionPlan(path="kernel", tm=8))
    _, d = _build_delta(lambda: op.update_values(M2))
    assert d == {"value_refresh": 1}
    fresh = ops.SpmvOperator.from_plan(M2, ExecutionPlan(path="kernel",
                                                         tm=8))
    X = jnp.asarray(np.random.default_rng(4).standard_normal((M2.m, 3))
                    .astype(np.float32))
    np.testing.assert_array_equal(np.asarray(op(X)), np.asarray(fresh(X)))


def test_update_values_rejects_different_structure():
    M1 = csrc.fem_band(64, 4, seed=5)
    M3 = csrc.fem_band(64, 4, seed=6)          # different pattern
    op = ops.SpmvOperator.from_plan(M1, ExecutionPlan(path="kernel", tm=8))
    with pytest.raises(ValueError):
        op.update_values(M3)


def test_refresh_rejects_numeric_symmetry_flip():
    """A symmetric->nonsymmetric value change alters the pack's streamed
    layout (vals_u conditional) — must rebuild, not refresh."""
    from repro.core import blockell
    M_sym = csrc.fem_band(48, 3, seed=1, numeric_symmetric=True)
    A = csrc.to_dense(M_sym)
    A_ns = np.where(A != 0, A + np.tril(np.ones_like(A), -1) * 0.5, 0.0)
    M_ns = csrc.from_dense(A_ns)
    assert S.structure_digest(M_sym) == S.structure_digest(M_ns)
    pack = blockell.pack(M_sym, tm=8)
    with pytest.raises(ValueError):
        blockell.refresh_values(pack, M_ns)


def test_schedule_npz_records_structure_digest(tmp_path):
    path = os.path.join(tmp_path, "plans.json")
    M = csrc.fem_band(48, 3, seed=2)
    cache = tuner.PlanCache(path=path)
    op = ops.SpmvOperator.from_plan(M, ExecutionPlan(path="kernel", tm=8),
                                    cache=cache)
    assert op.schedule.structure_digest == S.structure_digest(M)
    cache2 = tuner.PlanCache(path=path)
    sched = cache2.get_schedule(tuner.fingerprint(M), S.value_digest(M),
                                ExecutionPlan(path="kernel", tm=8))
    assert sched is not None
    assert sched.structure_digest == S.structure_digest(M)


# ---------------------------------------------------------------------------
# index_dtype through plans, candidates, and schedules
# ---------------------------------------------------------------------------

def test_plan_index_dtype_field_key_and_roundtrip():
    p = ExecutionPlan(path="kernel", index_dtype="int16")
    assert ":i16:" in p.key()
    assert ExecutionPlan.from_json(p.to_json()) == p
    with pytest.raises(ValueError):
        ExecutionPlan(index_dtype="int8")
    # old cache entries (no index_dtype key) deserialize to int32
    d = p.to_dict()
    del d["index_dtype"]
    assert ExecutionPlan.from_dict(d).index_dtype == "int32"


def test_enumerate_proposes_int16_where_pack_supports_it():
    M = csrc.fem_band(96, 4, seed=1)
    plans = tuner.enumerate_plans(tuner.stats_of(M), tms=(8,))
    kernel = [p for p in plans if p.path == "kernel"]
    assert {p.index_dtype for p in kernel} == {"int32", "int16"}
    # and the sweep can be restricted to int32 (legacy behavior)
    only32 = tuner.enumerate_plans(tuner.stats_of(M), tms=(8,),
                                   index_dtypes=("int32",))
    assert all(p.index_dtype == "int32" for p in only32)


def test_int16_infeasible_when_window_overflows():
    from repro.core.plan import feasible
    # the stream variant: a one-hot plan this wide is refused earlier, by
    # the kernel's VMEM window gate
    wide = ExecutionPlan(path="kernel", tm=128, w_cap=1 << 20,
                         index_dtype="int16", variant="stream")
    assert feasible(dataclasses.replace(wide, index_dtype="int32"),
                    n=60000, m=60000, bandwidth=40000)
    assert not feasible(wide, n=60000, m=60000, bandwidth=40000)


@pytest.mark.parametrize("path", ["kernel", "flat"])
def test_int16_plan_bit_identical_and_smaller_stream(path):
    M = csrc.skewed_band(128, 16, 3, seed=4)
    p32 = ExecutionPlan(path=path, tm=16)
    p16 = ExecutionPlan(path=path, tm=16, index_dtype="int16")
    # distinct schedule artifacts (the pack differs)
    assert S.plan_artifact_fields(p32) != S.plan_artifact_fields(p16)
    op32 = ops.SpmvOperator.from_plan(M, p32)
    op16 = ops.SpmvOperator.from_plan(M, p16)
    assert op16.pack.col_local.dtype == jnp.int16
    assert op16.pack.streamed_bytes() < op32.pack.streamed_bytes()
    x = jnp.asarray(np.random.default_rng(5).standard_normal(M.m)
                    .astype(np.float32))
    np.testing.assert_array_equal(np.asarray(op32(x)), np.asarray(op16(x)))


def test_int16_plan_reaches_distributed_flat_packs():
    """The shard-local flat layouts stream indices in the plan's dtype
    (and memoize per dtype), so a tuned int16 plan keeps its bandwidth win
    under the distributed strategies too."""
    M = csrc.fem_band(64, 4, seed=2)
    p16 = ExecutionPlan(path="flat", tm=16, index_dtype="int16")
    p32 = ExecutionPlan(path="flat", tm=16)
    sched = S.build_schedule(M, p16)
    fs16 = S.build_flat_shards(M, sched.partition, p16)
    fs32 = S.build_flat_shards(M, sched.partition, p32)
    assert fs16.col_local.dtype == jnp.int16
    assert fs32.col_local.dtype == jnp.int32        # distinct memo entries
    fh16 = S.build_flat_halo_layout(M, 2, p16)
    assert fh16.col_local.dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(fs16.col_local, np.int32),
                                  np.asarray(fs32.col_local))


def test_int16_schedule_disk_roundtrip_preserves_dtype(tmp_path):
    path = os.path.join(tmp_path, "plans.json")
    M = csrc.fem_band(64, 4, seed=9)
    plan = ExecutionPlan(path="kernel", tm=8, index_dtype="int16")
    cache = tuner.PlanCache(path=path)
    op1 = ops.SpmvOperator.from_plan(M, plan, cache=cache)
    cache2 = tuner.PlanCache(path=path)
    op2, d = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache2))
    assert d == {}, f"disk hit rebuilt: {d}"
    assert op2.pack.col_local.dtype == jnp.int16
    x = jnp.asarray(np.random.default_rng(6).standard_normal(M.m)
                    .astype(np.float32))
    np.testing.assert_array_equal(np.asarray(op1(x)), np.asarray(op2(x)))


# ---------------------------------------------------------------------------
# coloring provider through plans, schedules, and the disk cache
# ---------------------------------------------------------------------------

def test_plan_coloring_field_key_and_backcompat():
    """The coloring provider is a plan field: ':race' marks the colorful
    key, greedy keys stay byte-identical to pre-provider caches, and old
    cache JSONs (no 'coloring' entry) deserialize to greedy."""
    greedy = ExecutionPlan(path="colorful")
    race = ExecutionPlan(path="colorful", coloring="race")
    assert greedy.key() == "colorful:nnz:allreduce"      # unchanged key
    assert race.key() == "colorful:race:nnz:allreduce"
    assert ExecutionPlan.from_json(race.to_json()) == race
    with pytest.raises(ValueError):
        ExecutionPlan(path="colorful", coloring="rainbow")
    # pre-provider cache entries (no coloring key) deserialize to greedy
    d = greedy.to_dict()
    del d["coloring"]
    restored = ExecutionPlan.from_dict(d)
    assert restored.coloring == "greedy"
    assert restored.key() == "colorful:nnz:allreduce"
    # the provider only marks the path that consumes it
    assert ":race" not in ExecutionPlan(path="segment",
                                        coloring="race").key()


def test_coloring_provider_separates_schedule_keys():
    """Both providers' artifacts coexist in one cache: the provider joins
    the colorful path's artifact fields, so the schedule keys differ."""
    M = csrc.fem_band(48, 4, seed=3)
    greedy = ExecutionPlan(path="colorful")
    race = ExecutionPlan(path="colorful", coloring="race")
    assert S.plan_artifact_fields(greedy) != S.plan_artifact_fields(race)
    fp, dig = tuner.fingerprint(M), S.value_digest(M)
    assert (S.schedule_key(fp, dig, greedy, p=1)
            != S.schedule_key(fp, dig, race, p=1))


def test_colorful_race_schedule_roundtrips_zero_rebuild(tmp_path):
    """A colorful:race schedule survives the npz round-trip — provider and
    level-group metadata included — and a fresh cache object rebuilds
    nothing (the BUILD_COUNTS probe) while producing bit-identical SpMV."""
    path = os.path.join(tmp_path, "plans.json")
    M = csrc.fem_band(96, 6, seed=5)
    plan = ExecutionPlan(path="colorful", coloring="race")
    x = jnp.asarray(np.random.default_rng(4).standard_normal(M.m)
                    .astype(np.float32))
    cache = tuner.PlanCache(path=path)
    op1, d1 = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache))
    assert d1.get("coloring") == 1
    cache2 = tuner.PlanCache(path=path)          # "new process"
    op2, d2 = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M, plan, cache=cache2))
    assert d2 == {}, f"disk hit rebuilt: {d2}"
    col = op2.schedule.coloring
    assert col.provider == "race"
    assert col.level_of_row is not None and col.group_of_row is not None
    assert np.array_equal(col.color_of_row,
                          op1.schedule.coloring.color_of_row)
    assert verify_coloring(M, col)
    np.testing.assert_array_equal(np.asarray(op1(x)), np.asarray(op2(x)))


def test_race_colorful_spmv_matches_dense_oracle():
    """The chunk-aware RACE coloring executes exactly on the sum-combining
    scatter: colorful:race SpMV and SpMM match the dense oracle."""
    M = csrc.fem_band(80, 8, seed=6)
    A = csrc.to_dense(M)
    plan = ExecutionPlan(path="colorful", coloring="race")
    op = ops.SpmvOperator.from_plan(M, plan)
    X = np.random.default_rng(5).standard_normal((M.m, 3)).astype(
        np.float32)
    np.testing.assert_allclose(np.asarray(op(jnp.asarray(X))), A @ X,
                               rtol=2e-4, atol=2e-4)
    x = X[:, 0]
    np.testing.assert_allclose(np.asarray(op(jnp.asarray(x))), A @ x,
                               rtol=2e-4, atol=2e-4)


def test_enumerate_plans_emits_both_coloring_providers():
    M = csrc.fem_band(96, 4, seed=1)
    plans = tuner.enumerate_plans(tuner.stats_of(M), tms=(8,))
    colorful = [p for p in plans if p.path == "colorful"]
    assert {p.coloring for p in colorful} == {"greedy", "race"}
    # the sweep can be restricted to one provider (legacy behavior)
    only_greedy = tuner.enumerate_plans(tuner.stats_of(M), tms=(8,),
                                        colorings=("greedy",))
    assert all(p.coloring == "greedy" for p in only_greedy
               if p.path == "colorful")
