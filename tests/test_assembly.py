"""The FEM assembly subsystem (repro.assembly): conflict-free CSRC
construction feeding the SpMV stack.

Covers: mesh generator invariants, element-coloring conflict-freeness,
bit-for-bit agreement of the colored / private-buffer strategies with the
serial oracle (the dyadic stiffness synthesis makes float32 accumulation
order-independent, so equality is exact — any race or dropped
contribution fails hard), AssemblySchedule cache/disk round-trips with
zero-rebuild probes, assembled matrices through the SpMV dense oracle for
nrhs in {1, 3, 8}, and the end-to-end assemble → tune → solve pipeline
including the value-refresh fast path for time stepping."""
import os

import numpy as np
import jax.numpy as jnp
import pytest

from _propshim import given, settings, st
from repro.assembly import mesh as amesh
from repro.assembly import (assemble, assemble_mesh, assembly_schedule_for,
                            build_assembly_schedule, color_elements,
                            element_dofs, scatter_colored, scatter_private,
                            scatter_serial, scatter_sorted,
                            verify_element_coloring)
from repro.assembly import scatter as scatter_mod
from repro.core import csrc, schedule as S, tuner
from repro.core.plan import ExecutionPlan
from repro.core.solvers import cg_solve
from repro.kernels import ops


def _build_delta(fn):
    """Run fn and return (result, builds-that-happened) from the probe."""
    before = dict(S.BUILD_COUNTS)
    out = fn()
    after = dict(S.BUILD_COUNTS)
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in set(after) | set(before)}
    return out, {k: v for k, v in delta.items() if v}


MESHES = [
    ("tri", lambda: amesh.grid_tri(5)),
    ("quad", lambda: amesh.grid_quad(4)),
    ("tet", lambda: amesh.grid_tet(2)),
]
MESH_IDS = [n for n, _ in MESHES]


def _dense_oracle(mesh, ke, ndof_per_node=1):
    """Independent dense assembly: float64 loop over elements — shares no
    code with the scatter strategies."""
    ed = element_dofs(mesh.conn, ndof_per_node)
    n = mesh.num_nodes * ndof_per_node
    A = np.zeros((n, n), np.float64)
    for e in range(mesh.ne):
        dofs = ed[e]
        A[np.ix_(dofs, dofs)] += np.asarray(ke[e], np.float64)
    return A


# ---------------------------------------------------------------------------
# Mesh generators and stiffness synthesis
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,make", MESHES, ids=MESH_IDS)
def test_mesh_generators_wellformed(name, make):
    mesh = make()
    assert mesh.conn.min() >= 0
    assert mesh.conn.max() < mesh.num_nodes
    assert mesh.coords.shape == (mesh.num_nodes, mesh.dim)
    # every element's nodes are distinct
    for e in range(mesh.ne):
        assert len(set(mesh.conn[e].tolist())) == mesh.nen
    vols = amesh.element_volumes(mesh)
    assert (vols > 0).all(), f"{name}: non-positive element volume"


def test_tet_mesh_covers_the_cube():
    """Kuhn triangulation: 6 tets per cube, volumes sum to the domain."""
    mesh = amesh.grid_tet(2)
    assert mesh.ne == 6 * 2 * 2 * 2
    assert amesh.element_volumes(mesh).sum() == pytest.approx(8.0)


def test_stiffness_is_dyadic_and_symmetric():
    """The synthesis contract: entries are multiples of 1/64 (exact in
    float32, order-independent accumulation) and element-symmetric."""
    for name, make in MESHES:
        mesh = make()
        for ke in (amesh.poisson_stiffness(mesh, mass=0.5),
                   amesh.synthetic_stiffness(mesh, seed=3)):
            assert ke.dtype == np.float32
            scaled = np.asarray(ke, np.float64) * amesh.QUANTUM
            np.testing.assert_array_equal(scaled, np.round(scaled))
            np.testing.assert_array_equal(ke, np.swapaxes(ke, 1, 2))


def test_element_dofs_interleaved():
    conn = np.asarray([[0, 2, 3]])
    ed = element_dofs(conn, ndof_per_node=2)
    np.testing.assert_array_equal(ed, [[0, 1, 4, 5, 6, 7]])
    np.testing.assert_array_equal(element_dofs(conn, 1), conn)


# ---------------------------------------------------------------------------
# Element coloring (conflict graph)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,make", MESHES, ids=MESH_IDS)
def test_element_coloring_conflict_free(name, make):
    """Satellite invariant: no two same-color elements share a DOF, and
    every element is covered exactly once."""
    mesh = make()
    col = color_elements(mesh.conn)
    assert verify_element_coloring(mesh.conn, col)
    covered = sorted(np.concatenate(
        [col.rows(c) for c in range(col.num_colors)]).tolist())
    assert covered == list(range(mesh.ne))


def test_element_coloring_balancing_preserves_invariant():
    """Balancing adds no color and does not enlarge the largest class."""
    mesh = amesh.grid_tri(6)
    raw = color_elements(mesh.conn, balance=False)
    bal = color_elements(mesh.conn, balance=True)
    assert bal.num_colors <= raw.num_colors
    assert bal.class_sizes().max() <= raw.class_sizes().max()
    assert verify_element_coloring(mesh.conn, bal)


# Kuhn meshes from 2^3 to 16^3, a box of unequal sides, and every other
# generator of assembly/mesh.py
COLORING_MESHES = [
    ("tet2", lambda: amesh.grid_tet(2)),
    ("tet4", lambda: amesh.grid_tet(4)),
    ("tet8", lambda: amesh.grid_tet(8)),
    ("tet16", lambda: amesh.grid_tet(16)),
    ("tet3x5x2", lambda: amesh.grid_tet(3, 5, 2)),
    ("tri", lambda: amesh.grid_tri(7)),
    ("quad", lambda: amesh.grid_quad(6)),
]
COLORING_IDS = [n for n, _ in COLORING_MESHES]


def _oracle_verify(conn, col):
    """The plain per-element loop the vectorised verifier replaced: within
    a color, a node may be touched by one serial chunk only."""
    grp = col.group_of_row
    for c in range(col.num_colors):
        owner = {}
        for e in col.rows(c).tolist():
            g = int(grp[e]) if grp is not None else e
            for v in conn[e].tolist():
                if owner.setdefault(v, g) != g:
                    return False
    return True


def _old_greedy_colors(conn):
    """The palette of the element coloring before the vectorised one: the
    row colorer's greedy over the explicit element graph,
    largest-degree-first guarded by natural order, then balanced."""
    from repro.assembly.conflict import element_adjacency
    from repro.core.coloring import color_graph
    return color_graph(element_adjacency(conn), include_indirect=False,
                       order="degree", balance=True).num_colors


@pytest.mark.parametrize("name,make", COLORING_MESHES, ids=COLORING_IDS)
def test_vectorised_coloring_valid_and_deterministic(name, make):
    mesh = make()
    a = color_elements(mesh.conn)
    b = color_elements(mesh.conn)
    np.testing.assert_array_equal(a.color_of_row, b.color_of_row)
    np.testing.assert_array_equal(a.rows_by_color, b.rows_by_color)
    assert _oracle_verify(mesh.conn, a)
    assert verify_element_coloring(mesh.conn, a)
    assert sorted(a.rows_by_color.tolist()) == list(range(mesh.ne))
    assert (a.class_sizes() > 0).all()


@pytest.mark.parametrize("name,make", COLORING_MESHES, ids=COLORING_IDS)
def test_vectorised_palette_within_old_greedy(name, make):
    mesh = make()
    new = color_elements(mesh.conn).num_colors
    assert new <= 1.25 * _old_greedy_colors(mesh.conn), name


@pytest.mark.parametrize("ordered_rounds", [0, 2, 4096])
def test_random_priority_rounds_color_any_connectivity(ordered_rounds,
                                                       monkeypatch):
    """Rounds past ``ORDERED_MAX_ROUNDS`` take seeded random priorities;
    on an irregular connectivity (nodes of any degree, one element
    listing a node twice) the result is valid and the same on every
    call."""
    from repro.assembly import conflict
    monkeypatch.setattr(conflict, "ORDERED_MAX_ROUNDS", ordered_rounds)
    rng = np.random.default_rng(7)
    conn = rng.integers(0, 40, (300, 3)).astype(np.int32)
    conn[5] = [9, 9, 11]
    a = color_elements(conn)
    b = color_elements(conn)
    np.testing.assert_array_equal(a.color_of_row, b.color_of_row)
    assert _oracle_verify(conn, a)
    assert verify_element_coloring(conn, a)


def test_vectorised_coloring_needs_more_than_one_mask_word():
    """More than 64 elements at one node: the per-node masks grow a word,
    and the palette passes 64."""
    conn = np.stack([np.zeros(70, np.int32),
                     np.arange(1, 71, dtype=np.int32)], axis=1)
    col = color_elements(conn)
    assert col.num_colors == 70
    assert _oracle_verify(conn, col)


def _moved(col, e, c):
    """``col`` with element e put in class c (structure rebuilt)."""
    from repro.core.coloring import Coloring
    color = col.color_of_row.copy()
    color[e] = c
    order = np.argsort(color, kind="stable")
    ptr = np.concatenate([[0], np.cumsum(np.bincount(
        color, minlength=col.num_colors))])
    return Coloring(color_of_row=color, num_colors=col.num_colors,
                    rows_by_color=order, color_ptr=ptr,
                    provider=col.provider, level_of_row=col.level_of_row,
                    group_of_row=col.group_of_row)


@pytest.mark.parametrize("provider", ["greedy", "race"])
def test_verifier_agrees_with_the_oracle_loop(provider):
    mesh = amesh.grid_tet(3)
    col = color_elements(mesh.conn, provider=provider)
    assert verify_element_coloring(mesh.conn, col)
    assert _oracle_verify(mesh.conn, col)
    # break it: element 0 joins the class of an element sharing its node,
    # in another serial chunk
    e = 0
    grp = (col.group_of_row if col.group_of_row is not None
           else np.arange(mesh.ne))
    f = next(f for f in range(1, mesh.ne)
             if set(mesh.conn[f].tolist()) & set(mesh.conn[e].tolist())
             and grp[f] != grp[e]
             and col.color_of_row[f] != col.color_of_row[e])
    broken = _moved(col, e, int(col.color_of_row[f]))
    assert not _oracle_verify(mesh.conn, broken)
    assert not verify_element_coloring(mesh.conn, broken)


# ---------------------------------------------------------------------------
# Assembly strategies vs the serial oracle (exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,make", MESHES, ids=MESH_IDS)
def test_assembled_poisson_matches_dense_oracle(name, make):
    """Acceptance: the assembled Poisson matrix equals the independent
    dense float64 oracle bit-for-bit on every mesh generator."""
    mesh = make()
    ke = amesh.poisson_stiffness(mesh, mass=0.5)
    sched = build_assembly_schedule(mesh)
    M = assemble(sched, ke, strategy="colored")
    A = _dense_oracle(mesh, ke)
    np.testing.assert_array_equal(csrc.to_dense(M).astype(np.float64), A)
    assert M.numerically_symmetric


@pytest.mark.parametrize("name,make", [MESHES[0], MESHES[2]],
                         ids=["tri", "tet"])
def test_assembled_elasticity_matches_dense_oracle(name, make):
    """Vector-valued DOFs (ndof_per_node=2, the elasticity shape)."""
    mesh = make()
    ke = amesh.synthetic_stiffness(mesh, ndof_per_node=2, seed=7)
    sched = build_assembly_schedule(mesh, ndof_per_node=2)
    M = assemble(sched, ke, strategy="colored")
    A = _dense_oracle(mesh, ke, ndof_per_node=2)
    np.testing.assert_array_equal(csrc.to_dense(M).astype(np.float64), A)


@pytest.mark.parametrize("name,make", MESHES, ids=MESH_IDS)
def test_all_strategies_bit_identical(name, make):
    """Colored and private-buffer scatters must equal the serial oracle
    exactly — the race detector the dyadic synthesis enables."""
    mesh = make()
    ke = amesh.synthetic_stiffness(mesh, seed=11)
    sched = build_assembly_schedule(mesh)
    ref = scatter_serial(sched, ke)
    np.testing.assert_array_equal(np.asarray(scatter_colored(sched, ke)),
                                  ref)
    np.testing.assert_array_equal(np.asarray(scatter_sorted(sched, ke)),
                                  ref)
    np.testing.assert_array_equal(np.asarray(scatter_private(sched, ke)),
                                  ref)


def test_private_buffer_width_does_not_change_result():
    mesh = amesh.grid_tri(5)
    ke = amesh.synthetic_stiffness(mesh, seed=2)
    ref = scatter_serial(build_assembly_schedule(mesh), ke)
    for nb in (1, 3, 16, 1000):
        sched = build_assembly_schedule(mesh, num_buffers=nb)
        assert sched.num_buffers <= mesh.ne
        np.testing.assert_array_equal(
            np.asarray(scatter_private(sched, ke)), ref)


@settings(max_examples=4, deadline=None)
@given(st.integers(2, 7), st.integers(0, 1000))
def test_property_random_tri_assembly_exact(nx, seed):
    mesh = amesh.grid_tri(nx)
    ke = amesh.synthetic_stiffness(mesh, seed=seed)
    sched = build_assembly_schedule(mesh)
    ref = scatter_serial(sched, ke)
    np.testing.assert_array_equal(np.asarray(scatter_colored(sched, ke)),
                                  ref)
    A = _dense_oracle(mesh, ke)
    M = assemble(sched, ke)
    np.testing.assert_array_equal(csrc.to_dense(M).astype(np.float64), A)


# ---------------------------------------------------------------------------
# AssemblySchedule caching (PlanCache, disk, zero-rebuild probes)
# ---------------------------------------------------------------------------

def test_assembly_schedule_cache_hit_zero_builds():
    mesh = amesh.grid_tri(5)
    cache = tuner.PlanCache()
    _, d1 = _build_delta(lambda: assembly_schedule_for(mesh, cache=cache))
    assert d1.get("assembly_schedule") == 1
    assert d1.get("element_coloring") == 1
    _, d2 = _build_delta(lambda: assembly_schedule_for(mesh, cache=cache))
    assert d2 == {}, f"cache hit rebuilt: {d2}"
    assert cache.assembly_hits == 1


def test_assembly_cache_hits_when_fewer_elements_than_buffers():
    """Regression: the builder clamps num_buffers to ne; the cache lookup
    must use the same clamp or the key never matches (tiny meshes would
    silently rebuild the schedule every step)."""
    mesh = amesh.grid_tri(1)                   # ne=2 < default 8 buffers
    cache = tuner.PlanCache()
    s1, d1 = _build_delta(lambda: assembly_schedule_for(mesh, cache=cache))
    assert s1.num_buffers == mesh.ne
    assert d1.get("assembly_schedule") == 1
    s2, d2 = _build_delta(lambda: assembly_schedule_for(mesh, cache=cache))
    assert d2 == {} and s2 is s1, f"tiny-mesh cache miss: {d2}"


def test_assembly_schedule_npz_roundtrip_through_disk_cache(tmp_path):
    """A fresh process (new cache object on the same file) loads the npz
    and rebuilds nothing; assembled matrices are bit-identical."""
    path = os.path.join(tmp_path, "plans.json")
    mesh = amesh.grid_tet(2)
    ke = amesh.synthetic_stiffness(mesh, seed=5)
    cache = tuner.PlanCache(path=path)
    s1 = assembly_schedule_for(mesh, cache=cache)
    cache2 = tuner.PlanCache(path=path)            # "new process"
    s2, d = _build_delta(lambda: assembly_schedule_for(mesh, cache=cache2))
    assert d == {}, f"disk hit rebuilt: {d}"
    for f in ("ia", "ja", "targets", "color_slots", "color_targets",
              "sorted_perm", "sorted_targets"):
        np.testing.assert_array_equal(getattr(s1, f), getattr(s2, f))
        assert getattr(s1, f).dtype == getattr(s2, f).dtype, f
    np.testing.assert_array_equal(csrc.to_dense(assemble(s1, ke)),
                                  csrc.to_dense(assemble(s2, ke)))


def test_assembly_version_mismatch_invalidates(tmp_path, monkeypatch):
    path = os.path.join(tmp_path, "plans.json")
    mesh = amesh.grid_tri(4)
    cache = tuner.PlanCache(path=path)
    assembly_schedule_for(mesh, cache=cache)
    monkeypatch.setattr(scatter_mod, "ASSEMBLY_VERSION",
                        scatter_mod.ASSEMBLY_VERSION + 1)
    cache2 = tuner.PlanCache(path=path)
    _, d = _build_delta(lambda: assembly_schedule_for(mesh, cache=cache2))
    assert d.get("assembly_schedule") == 1     # rebuilt, not crashed


def test_structure_digest_discriminates():
    m1, m2 = amesh.grid_tri(4), amesh.grid_tri(5)
    from repro.assembly import structure_digest
    assert structure_digest(m1.conn) != structure_digest(m2.conn)
    assert (structure_digest(m1.conn, ndof_per_node=2)
            != structure_digest(m1.conn))
    assert structure_digest(m1.conn) == structure_digest(m1.conn.copy())


# ---------------------------------------------------------------------------
# Assembled matrices through the SpMV stack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nrhs", [1, 3, 8])
def test_assembled_matrix_all_spmv_plans_dense_oracle(nrhs):
    """Acceptance: the assembled matrix executes through every feasible
    registry path (kernel/flat/segment/colorful, int32 and int16 index
    streams) and matches the dense oracle for nrhs in {1, 3, 8}."""
    mesh = amesh.grid_tri(5)
    ke = amesh.poisson_stiffness(mesh, mass=0.5)
    M = assemble(build_assembly_schedule(mesh), ke)
    A = csrc.to_dense(M).astype(np.float64)
    X = np.random.default_rng(nrhs).standard_normal(
        (M.m, nrhs)).astype(np.float32)
    Y_ref = A @ X.astype(np.float64)
    scale = max(1.0, np.abs(Y_ref).max())
    plans = tuner.enumerate_plans(tuner.stats_of(M), tms=(8,))
    assert any(p.path == "kernel" for p in plans)
    for plan in plans:
        op = ops.SpmvOperator.from_plan(M, plan)
        Y = np.asarray(op(jnp.asarray(X)), dtype=np.float64)
        np.testing.assert_allclose(Y / scale, Y_ref / scale,
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"plan {plan.key()}")


def test_time_stepping_reuses_everything():
    """FEM time stepping: re-assembly with unchanged connectivity reuses
    the assembly schedule AND the SpMV schedule — the second step performs
    exactly one value refresh, zero structural rebuilds."""
    mesh = amesh.grid_tri(6)
    cache = tuner.PlanCache()
    plan = ExecutionPlan(path="kernel", tm=8)
    sched = assembly_schedule_for(mesh, cache=cache)

    def step(t):
        ke = amesh.poisson_stiffness(mesh, mass=0.5 + 0.25 * t)
        return assemble(sched, ke, strategy="colored")

    # the value-refresh fast path: one refresh probe per assemble, zero
    # structural rebuilds (the kernel packs are reused as-is)
    _, da = _build_delta(lambda: step(0))
    assert da == {"assembly_value_refresh": 1}, f"refresh rebuilt: {da}"

    M0 = step(0)
    op, d0 = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M0, plan, cache=cache))
    assert d0.get("pack") == 1
    M1 = step(1)
    op1, d1 = _build_delta(
        lambda: ops.SpmvOperator.from_plan(M1, plan, cache=cache))
    assert d1 == {"value_refresh": 1}, f"structural rebuild: {d1}"
    x = jnp.asarray(np.random.default_rng(0).standard_normal(M1.m)
                    .astype(np.float32))
    ref = csrc.to_dense(M1).astype(np.float64) @ np.asarray(x, np.float64)
    scale = max(1.0, np.abs(ref).max())
    np.testing.assert_allclose(
        np.asarray(op1(x), np.float64) / scale, ref / scale,
        rtol=2e-4, atol=2e-4)
    # in-place refresh of the existing operator: same probe, same result
    _, d2 = _build_delta(lambda: op.update_values(M1))
    assert d2 == {"value_refresh": 1}
    np.testing.assert_array_equal(np.asarray(op(x)), np.asarray(op1(x)))


def test_end_to_end_assemble_tune_solve():
    """The acceptance demo: assemble a Poisson system from a mesh, tune
    it, solve with cg_solve; the solution matches the dense solve."""
    mesh = amesh.grid_tri(6)
    ke = amesh.poisson_stiffness(mesh, mass=1.0)
    cache = tuner.PlanCache()
    M, sched = assemble_mesh(mesh, ke, cache=cache)
    # colored assembly matches the serial oracle exactly
    np.testing.assert_array_equal(
        csrc.to_dense(M), csrc.to_dense(assemble(sched, ke, "serial")))
    # tune (deterministic injected measure), then solve through the cache
    res = tuner.tune(M, cache=cache,
                     measure=lambda op, x: 1.0 if op.plan.path == "kernel"
                     else 2.0)
    assert res.plan.path == "kernel"
    A = csrc.to_dense(M).astype(np.float64)
    x_true = np.random.default_rng(3).standard_normal(M.n)
    b = jnp.asarray(A @ x_true, dtype=jnp.float32)
    sol, op = cg_solve(M, b, cache=cache, tol=1e-7, maxiter=2000)
    assert bool(sol.converged)
    assert op.plan == res.plan                 # solved with the tuned plan
    np.testing.assert_allclose(np.asarray(sol.x, np.float64), x_true,
                               rtol=5e-3, atol=5e-3)


def test_serving_time_stepping_value_refresh():
    """Re-registering a re-assembled (same-structure) matrix in the
    serving engine refreshes value streams only — the satellite's
    zero-structural-rebuild probe."""
    from repro.serve.engine import SpmvServingEngine
    mesh = amesh.grid_tri(6)
    sched = build_assembly_schedule(mesh)
    M0 = assemble(sched, amesh.poisson_stiffness(mesh, mass=0.5))
    M1 = assemble(sched, amesh.poisson_stiffness(mesh, mass=1.5))
    eng = SpmvServingEngine(cache=tuner.PlanCache())
    eng.register("fem", M0)
    _, d = _build_delta(lambda: eng.register("fem", M1))
    assert d == {"value_refresh": 1}, f"structural rebuild: {d}"
    # the delta wraps an assemble() call too: exactly one assembly value
    # refresh fires (the satellite probe) and no pack/schedule rebuilds
    _, d2 = _build_delta(lambda: eng.update_values(
        "fem", assemble(sched, amesh.poisson_stiffness(mesh, mass=2.5))))
    assert d2 == {"value_refresh": 1, "assembly_value_refresh": 1}
    x = np.random.default_rng(1).standard_normal(M1.m).astype(np.float32)
    uid = eng.submit("fem", x)
    out = eng.run_until_drained()
    M2 = assemble(sched, amesh.poisson_stiffness(mesh, mass=2.5))
    np.testing.assert_allclose(out[uid], csrc.to_dense(M2) @ x,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# RACE element coloring through assembly schedules and the cache
# ---------------------------------------------------------------------------

def test_race_halves_tet_element_palette():
    """The acceptance property: on the tet mesh ~24 elements share one
    node (a 24-clique), so any classic coloring needs a palette past 24 —
    RACE's level groups need at most half of greedy's, and the coloring
    stays valid under the chunk-aware invariant."""
    mesh = amesh.grid_tet(3)
    greedy = color_elements(mesh.conn, provider="greedy")
    race = color_elements(mesh.conn, provider="race")
    assert race.num_colors * 2 <= greedy.num_colors
    assert verify_element_coloring(mesh.conn, greedy)
    assert verify_element_coloring(mesh.conn, race)
    assert race.provider == "race"
    assert race.group_of_row is not None


@pytest.mark.parametrize("name,make", MESHES, ids=MESH_IDS)
def test_race_colored_assembly_bit_identical(name, make):
    """RACE's weaker intra-chunk guarantee is exact on the sum-combining
    scatter: colored assembly under the race provider matches the serial
    oracle bit for bit on every mesh class."""
    mesh = make()
    ke = amesh.synthetic_stiffness(mesh, seed=11)
    sched = build_assembly_schedule(mesh.conn, coloring_provider="race")
    assert sched.coloring.provider == "race"
    got = scatter_colored(sched, ke)
    want = scatter_serial(sched, ke)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_assembly_key_separates_providers():
    """Both providers' schedules coexist: the provider suffixes the
    assembly cache key (greedy keys stay byte-identical to pre-provider
    caches)."""
    from repro.assembly.scatter import assembly_key
    dig = "abc123"
    assert assembly_key(dig, 8, "greedy") == assembly_key(dig, 8)
    assert assembly_key(dig, 8, "race") != assembly_key(dig, 8, "greedy")
    assert assembly_key(dig, 8, "race").endswith(".race")


def test_race_assembly_schedule_roundtrips_zero_rebuild(tmp_path):
    """A race AssemblySchedule survives the npz round-trip with provider
    and level-group metadata, a fresh cache rebuilds nothing, and both
    providers' artifacts live side by side in one cache file."""
    path = os.path.join(tmp_path, "plans.json")
    mesh = amesh.grid_tet(2)
    ke = amesh.synthetic_stiffness(mesh, seed=7)
    cache = tuner.PlanCache(path=path)
    s_greedy = assembly_schedule_for(mesh, cache=cache)
    s1, d1 = _build_delta(lambda: assembly_schedule_for(
        mesh, cache=cache, coloring_provider="race"))
    assert d1.get("element_coloring") == 1     # distinct artifact built
    cache2 = tuner.PlanCache(path=path)            # "new process"
    s2, d2 = _build_delta(lambda: assembly_schedule_for(
        mesh, cache=cache2, coloring_provider="race"))
    assert d2 == {}, f"disk hit rebuilt: {d2}"
    col = s2.coloring
    assert col.provider == "race"
    assert col.level_of_row is not None and col.group_of_row is not None
    np.testing.assert_array_equal(col.color_of_row,
                                  s1.coloring.color_of_row)
    assert s_greedy.coloring.provider == "greedy"
    np.testing.assert_array_equal(csrc.to_dense(assemble(s1, ke)),
                                  csrc.to_dense(assemble(s2, ke)))
