"""The fused assembly-scatter kernel layer (repro.kernels.assembly_scatter
+ the scatter.py wiring): colored-batch stream/onehot bodies, the
sorted-slot strategy, int16 index gating, the value-refresh probe, and
predict-then-measure strategy selection.

Everything numerical is asserted bit-for-bit against the serial
``np.add.at`` oracle — the dyadic stiffness synthesis makes float32
accumulation order-independent, so any dropped sentinel, mis-gated
upcast, or pack corruption fails hard, not approximately."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _propshim import given, settings, st
from repro import obs
from repro.assembly import mesh as amesh
from repro.assembly import (assemble, build_assembly_schedule,
                            color_elements, scatter_colored,
                            scatter_colored_percolor, scatter_private,
                            scatter_serial, scatter_sorted, tune_assembly)
from repro.assembly.scatter import (ASSEMBLY_CANDIDATES, STRATEGIES,
                                    AssemblySchedule)
from repro.core import schedule as S, tuner
from repro.core.coloring import Coloring
from repro.kernels import assembly_scatter as akern
from repro.roofline import cost_model


MESHES = [
    ("tri", lambda: amesh.grid_tri(5)),
    ("quad", lambda: amesh.grid_quad(4)),
    ("tet", lambda: amesh.grid_tet(2)),
]
MESH_IDS = [n for n, _ in MESHES]

# every (strategy, variant) executor the PR ships, plus the in-grid
# Pallas bodies run through the emulated grid
COMBOS = [("colored", "stream"), ("colored", "onehot"),
          ("colored", "percolor"), ("sorted", "stream"),
          ("private", "vmap")]
COMBO_IDS = [f"{s}-{v}" for s, v in COMBOS]


def _build_delta(fn):
    before = dict(S.BUILD_COUNTS)
    out = fn()
    after = dict(S.BUILD_COUNTS)
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in set(after) | set(before)}
    return out, {k: v for k, v in delta.items() if v}


def _scatter(sched, ke, strategy, variant):
    if strategy == "colored":
        return scatter_colored(sched, ke, variant=variant)
    if strategy == "sorted":
        return scatter_sorted(sched, ke)
    if strategy == "private":
        return scatter_private(sched, ke)
    return scatter_serial(sched, ke)


# ---------------------------------------------------------------------------
# Bit-identity: every strategy × variant × mesh class vs the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy,variant", COMBOS, ids=COMBO_IDS)
@pytest.mark.parametrize("name,make", MESHES, ids=MESH_IDS)
def test_every_executor_bit_identical(name, make, strategy, variant):
    mesh = make()
    ke = amesh.synthetic_stiffness(mesh, seed=13)
    sched = build_assembly_schedule(mesh)
    ref = scatter_serial(sched, ke)
    got = np.asarray(_scatter(sched, ke, strategy, variant))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("strategy,variant", COMBOS, ids=COMBO_IDS)
@pytest.mark.parametrize("ndof", [1, 3])
@pytest.mark.parametrize("nx", [2, 3])
def test_every_executor_bit_identical_on_kuhn_meshes(nx, ndof, strategy,
                                                      variant):
    """Every executor, fed the vectorised element coloring of a Kuhn mesh
    with scalar and elasticity DOFs, matches the serial oracle."""
    mesh = amesh.grid_tet(nx, 2, 2)
    ke = amesh.synthetic_stiffness(mesh, ndof_per_node=ndof, seed=11)
    sched = build_assembly_schedule(mesh, ndof_per_node=ndof)
    assert sched.coloring.provider == "greedy"
    np.testing.assert_array_equal(
        np.asarray(_scatter(sched, ke, strategy, variant)),
        scatter_serial(sched, ke))


@pytest.mark.parametrize("variant", ["onehot"])
@pytest.mark.parametrize("name,make", [MESHES[0], MESHES[2]],
                         ids=["tri", "tet"])
def test_pallas_grid_bodies_match_oracle(name, make, variant):
    """The one-hot Pallas grid (output tile x contribution chunk) through
    the interpreter — the kernel the compiled TPU target runs — matches
    the oracle bit for bit."""
    mesh = make()
    ke = amesh.synthetic_stiffness(mesh, seed=5)
    sched = build_assembly_schedule(mesh)
    ref = scatter_serial(sched, ke)
    got = np.asarray(akern.colored_scatter(
        sched.color_slots, sched.color_targets, jnp.asarray(ke),
        sched.size, variant=variant, interpret=True))
    np.testing.assert_array_equal(got, ref)


def test_colored_kernels_are_jit_compatible():
    mesh = amesh.grid_tet(2)
    ke = amesh.synthetic_stiffness(mesh, seed=3)
    sched = build_assembly_schedule(mesh)
    ref = scatter_serial(sched, ke)
    for fn in (jax.jit(lambda k: scatter_colored(sched, k)),
               jax.jit(lambda k: scatter_sorted(sched, k))):
        np.testing.assert_array_equal(np.asarray(fn(jnp.asarray(ke))),
                                      ref)


def test_race_coloring_through_the_fused_kernels():
    """RACE packs (fewer, larger colors) through both kernel variants."""
    mesh = amesh.grid_tet(2)
    ke = amesh.synthetic_stiffness(mesh, seed=17)
    sched = build_assembly_schedule(mesh.conn, coloring_provider="race")
    ref = scatter_serial(sched, ke)
    for variant in ("stream", "onehot"):
        np.testing.assert_array_equal(
            np.asarray(scatter_colored(sched, ke, variant=variant)), ref)
    np.testing.assert_array_equal(
        np.asarray(scatter_sorted(sched, ke)), ref)


# ---------------------------------------------------------------------------
# Property sweep + edge cases (satellite)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.sampled_from(["tri", "quad", "tet"]), st.integers(2, 5),
       st.integers(0, 10_000))
def test_property_random_meshes_all_strategies_exact(kind, nx, seed):
    """Random structured meshes × all three strategies × both kernel
    variants: bit-identity vs the serial oracle, every draw."""
    gen = {"tri": amesh.grid_tri, "quad": amesh.grid_quad,
           "tet": lambda s: amesh.grid_tet(max(2, s // 2))}[kind]
    mesh = gen(nx)
    ke = amesh.synthetic_stiffness(mesh, seed=seed)
    sched = build_assembly_schedule(mesh)
    ref = scatter_serial(sched, ke)
    for strategy, variant in COMBOS:
        got = np.asarray(_scatter(sched, ke, strategy, variant))
        np.testing.assert_array_equal(
            got, ref, err_msg=f"{kind} nx={nx} seed={seed} "
                              f"{strategy}/{variant}")


def test_empty_color_class_is_inert():
    """A palette entry with zero elements (legal after balancing) must
    contribute nothing: its pack row is all sentinels."""
    mesh = amesh.grid_tri(4)
    ke = amesh.synthetic_stiffness(mesh, seed=9)
    col = color_elements(mesh.conn)
    padded = Coloring(
        color_of_row=col.color_of_row,
        num_colors=col.num_colors + 1,
        rows_by_color=col.rows_by_color,
        color_ptr=np.append(col.color_ptr, col.color_ptr[-1]),
        provider=col.provider)
    sched = build_assembly_schedule(mesh, coloring=padded)
    assert sched.color_slots.shape[0] == col.num_colors + 1
    # the empty color's row is pure sentinel padding
    assert (sched.color_slots[-1] == sched.targets.size).all()
    assert (sched.color_targets[-1] == sched.size).all()
    ref = scatter_serial(sched, ke)
    for variant in ("stream", "onehot", "percolor"):
        np.testing.assert_array_equal(
            np.asarray(scatter_colored(sched, ke, variant=variant)), ref)


def test_single_element_mesh():
    """ne=1 degenerate schedule: one color, every strategy exact."""
    conn = np.asarray([[0, 1, 2]])
    ke = np.asarray([[[2.0, -1.0, -1.0], [-1.0, 2.0, -1.0],
                      [-1.0, -1.0, 2.0]]], np.float32) / 4
    sched = build_assembly_schedule(conn)
    assert sched.ne == 1 and sched.coloring.num_colors == 1
    ref = scatter_serial(sched, ke)
    for strategy, variant in COMBOS:
        np.testing.assert_array_equal(
            np.asarray(_scatter(sched, ke, strategy, variant)), ref,
            err_msg=f"{strategy}/{variant}")


# ---------------------------------------------------------------------------
# int16 index gating (satellite)
# ---------------------------------------------------------------------------

def test_int16_gate_small_mesh_narrows_all_streams():
    sched = build_assembly_schedule(amesh.grid_tri(5))
    assert sched.size <= np.iinfo(np.int16).max
    assert sched.color_slots.dtype == np.int16
    assert sched.color_targets.dtype == np.int16
    assert sched.sorted_perm.dtype == np.int16
    assert sched.sorted_targets.dtype == np.int16


def test_int16_gate_overflow_upcasts_targets_only():
    """A schedule whose unified vector exceeds the int16 range but whose
    contribution count does not: target streams widen to int32, slot
    streams stay int16 — the gates are per stream, like SpMV."""
    i16 = np.iinfo(np.int16).max
    conn = np.asarray([[0, 1, i16]])        # n = 32768 > int16 max
    sched = build_assembly_schedule(conn)
    assert sched.size > i16 and sched.targets.size <= i16
    assert sched.color_targets.dtype == np.int32
    assert sched.sorted_targets.dtype == np.int32
    assert sched.color_slots.dtype == np.int16
    assert sched.sorted_perm.dtype == np.int16
    # upcast correctness: the wide-target kernels still match the oracle
    ke = np.asarray([[[2.0, -0.5, -0.25], [-0.5, 1.0, -0.125],
                      [-0.25, -0.125, 3.0]]], np.float32)
    ref = scatter_serial(sched, ke)
    for strategy, variant in COMBOS:
        np.testing.assert_array_equal(
            np.asarray(_scatter(sched, ke, strategy, variant)), ref,
            err_msg=f"{strategy}/{variant}")


def test_int16_pack_dtypes_survive_npz(tmp_path):
    path = os.path.join(tmp_path, "asm.npz")
    sched = build_assembly_schedule(amesh.grid_quad(4))
    sched.save_npz(path)
    back = AssemblySchedule.load_npz(path)
    for f in ("color_slots", "color_targets", "sorted_perm",
              "sorted_targets"):
        assert getattr(back, f).dtype == getattr(sched, f).dtype, f
        np.testing.assert_array_equal(getattr(back, f),
                                      getattr(sched, f))


# ---------------------------------------------------------------------------
# Value-refresh instrumentation (satellite)
# ---------------------------------------------------------------------------

def test_assemble_counts_one_value_refresh_and_zero_rebuilds():
    mesh = amesh.grid_tri(5)
    ke = amesh.poisson_stiffness(mesh, mass=1.0)
    sched, d0 = _build_delta(lambda: build_assembly_schedule(mesh))
    assert d0.get("assembly_color_pack") == 1
    assert d0.get("assembly_sorted_pack") == 1
    for strategy in STRATEGIES:
        _, d = _build_delta(lambda: assemble(sched, ke,
                                             strategy=strategy))
        assert d == {"assembly_value_refresh": 1}, (strategy, d)


def test_assemble_observes_span_and_histogram():
    mesh = amesh.grid_tri(4)
    ke = amesh.poisson_stiffness(mesh, mass=1.0)
    sched = build_assembly_schedule(mesh)
    snap0 = obs.snapshot()
    assemble(sched, ke, strategy="sorted")
    assemble(sched, ke, strategy="colored", variant="onehot")
    d = obs.snapshot().diff(snap0)
    h_sorted = d.merged_hist("assembly_scatter_seconds",
                             strategy="sorted", variant="stream")
    h_onehot = d.merged_hist("assembly_scatter_seconds",
                             strategy="colored", variant="onehot")
    assert h_sorted.get("count") == 1, h_sorted
    assert h_onehot.get("count") == 1, h_onehot
    assert d.total("build_total", kind="assembly_value_refresh") == 2


# ---------------------------------------------------------------------------
# Predict-then-measure strategy selection + cost model
# ---------------------------------------------------------------------------

def test_assembly_cost_prices_every_candidate():
    sched = build_assembly_schedule(amesh.grid_tet(2))
    priced = cost_model.rank_assembly_candidates(sched,
                                                 ASSEMBLY_CANDIDATES)
    assert len(priced) == len(ASSEMBLY_CANDIDATES)
    for (s, v), est in priced:
        assert est.predicted_s > 0 and est.bytes > 0, (s, v)
    by_key = {f"{s}/{v}": est for (s, v), est in priced}
    # the one-hot mask build makes that variant compute-bound; the
    # per-color baseline pays the palette launch term above the fused
    # stream kernel
    assert by_key["colored/onehot"].bound == "compute"
    assert (by_key["colored/percolor"].predicted_s
            > by_key["colored/stream"].predicted_s)
    # sorted-slot streams the fewest bytes — no pack padding at all
    assert by_key["sorted/stream"].bytes <= by_key["colored/stream"].bytes


def test_onehot_refused_past_its_grid_gate(monkeypatch):
    """``colored/onehot`` leaves the pool when its (tile, chunk) grid
    passes ONEHOT_MAX_PROGRAMS, decided by the schedule's sizes alone."""
    from repro.assembly import scatter as scatter_mod
    sched = build_assembly_schedule(amesh.grid_tet(2))
    programs = akern.onehot_programs(sched.color_slots.size, sched.size)
    assert scatter_mod.assembly_candidates(sched) == list(
        ASSEMBLY_CANDIDATES)
    monkeypatch.setattr(scatter_mod, "ONEHOT_MAX_PROGRAMS", programs - 1)
    pool = scatter_mod.assembly_candidates(sched)
    assert ("colored", "onehot") not in pool
    assert len(pool) == len(ASSEMBLY_CANDIDATES) - 1
    d0 = obs.snapshot()
    res = tune_assembly(sched, amesh.synthetic_stiffness(
        amesh.grid_tet(2), seed=1), measure=lambda fn, kej: 1.0)
    assert "colored/onehot" not in res.predictions_s
    d = obs.snapshot().diff(d0)
    assert d.value("assembly_tuner_candidates_total",
                   outcome="refused") == 1


def test_onehot_programs_is_the_kernels_grid():
    sched = build_assembly_schedule(amesh.grid_tri(5))
    slots = sched.color_slots.size
    chunk = min(akern.ONEHOT_CHUNK, -(-slots // 128) * 128)
    tiles = -(-(sched.size + 1) // akern.ONEHOT_TILE)
    assert akern.onehot_programs(slots, sched.size) == (
        tiles * -(-slots // chunk))


def test_tune_assembly_picks_injected_winner_and_caches(tmp_path):
    path = os.path.join(tmp_path, "plans.json")
    mesh = amesh.grid_tri(5)
    ke = amesh.poisson_stiffness(mesh, mass=1.0)
    sched = build_assembly_schedule(mesh)
    cache = tuner.PlanCache(path=path)

    def measure(fn, kej):                  # deterministic constant clock
        out = np.asarray(fn(kej))          # executor must actually run
        assert out.shape == (sched.size,)
        return 1.0

    res = tune_assembly(sched, ke, cache=cache, measure=measure)
    assert not res.cached
    assert (res.strategy, res.variant) in ASSEMBLY_CANDIDATES
    assert res.predictions_s.keys() >= res.timings_s.keys()
    assert set(res.roofline_fraction) == set(res.timings_s)
    # every strategy family was measured at least once (no family is
    # pruned unseen)
    measured_strategies = {k.split("/")[0] for k in res.timings_s}
    assert measured_strategies == {"colored", "sorted", "private"}
    # second call: pure cache hit, nothing measured
    res2 = tune_assembly(sched, ke, cache=cache,
                         measure=lambda fn, k: pytest.fail("measured"))
    assert res2.cached and res2.key() == res.key()
    # the record survives the disk round-trip ("new process")
    cache2 = tuner.PlanCache(path=path)
    res3 = tune_assembly(sched, ke, cache=cache2,
                         measure=lambda fn, k: pytest.fail("measured"))
    assert res3.cached and res3.key() == res.key()
    assert res3.roofline_fraction == res.roofline_fraction


def test_tune_assembly_winner_beats_percolor_on_tet():
    """The acceptance property, as a live measurement: on the tet mesh
    the tuned fused kernel is faster at steady state than the legacy
    per-color XLA scatter baseline."""
    mesh = amesh.grid_tet(3)
    ke = amesh.synthetic_stiffness(mesh, seed=1)
    sched = build_assembly_schedule(mesh)
    res = tune_assembly(sched, ke, repeats=3)
    assert res.key() != "colored/percolor"
    if "colored/percolor" in res.timings_s:
        assert (res.timings_s[res.key()]
                < res.timings_s["colored/percolor"])
