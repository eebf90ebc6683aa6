"""``fem_tet113``'s configuration module is ``fem_tet``'s generator and
reference: at one small box both give the same mesh, element tables (in
float16 for ``fem_tet113``, the same values) and float64 matrix, and the
runner makes the same float32 element matrices from either."""
import numpy as np

import harness


def _cell(workload):
    cell = harness.find_cell(harness.load_spec(), workload)
    cell.config = dict(cell.config, nx=3, ny=2, nz=4)
    return cell


def test_fem_tet113_is_fem_tet_at_its_own_size():
    big, small = _cell("fem_tet113.step"), _cell("fem_tet.step")
    cfg, mod, ref_mod = big.config, big.config_mod, small.config_mod
    for a, b in zip(mod.mesh(cfg), ref_mod.mesh(small.config)):
        np.testing.assert_array_equal(a, b)
    lap, mass = mod.element_tables(cfg)
    lap0, mass0 = ref_mod.element_tables(small.config)
    assert lap.dtype == np.float16
    np.testing.assert_array_equal(lap.astype(np.float32), lap0)
    assert mass == mass0
    coords, conn, _ = mod.mesh(cfg)
    kappa = mod.kappa_ring(cfg, np.random.default_rng(3), 2)
    np.testing.assert_array_equal(
        kappa, ref_mod.kappa_ring(small.config, np.random.default_rng(3), 2))
    np.testing.assert_array_equal(
        mod.lumped_mass(cfg, conn, coords.shape[0]),
        ref_mod.lumped_mass(small.config, conn, coords.shape[0]))
    A = mod.reference(cfg, coords, conn, kappa[1])
    A0 = ref_mod.reference(small.config, coords, conn, kappa[1])
    assert abs(A - A0).max() == 0.0


def test_fem_tet113_states_the_uncut_box():
    cell = harness.find_cell(harness.load_spec(), "fem_tet113.step")
    cfg = cell.config
    assert (cfg["nx"], cfg["ny"], cfg["nz"]) == (113, 113, 113)
    assert cfg["reduced"] == {}
    assert cell.traffic["ring"] == 4 and cell.traffic["sample"] == 2
    base = harness.find_cell(harness.load_spec(), "fem_tet.step").config
    for key in ("spacing", "mass", "kappa_choices", "quantum", "dtype",
                "deployment", "assumed", "source"):
        assert cfg[key] == base[key], key


def test_fem_tet113_ring_is_fem_tet_ring(tmp_path):
    """The step runner's element-matrix ring, made from the float16
    tables, equals the one made from ``fem_tet``'s float32 tables."""
    import jax.numpy as jnp
    rings = []
    for workload in ("fem_tet113.step", "fem_tet.step"):
        cell = _cell(workload)
        runner = cell.runner.Runner(cell, tmp_path / workload)
        runner.setup_static()
        runner.setup_seed(2 ** 35 + 1)
        rings.append([np.asarray(k) for k in runner.ke])
        assert runner.ke[0].dtype == jnp.float32
    for a, b in zip(*rings):
        np.testing.assert_array_equal(a, b)
