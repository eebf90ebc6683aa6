"""The configurations' generators against their plain references."""
import numpy as np
import pytest
import scipy.sparse as sp

from conftest import small_cell


def full_of(n, ia, ja, ad, al, au):
    rows = np.repeat(np.arange(n), np.diff(ia))
    r = np.concatenate([np.arange(n), rows, ja])
    c = np.concatenate([np.arange(n), ja, rows])
    return sp.csr_matrix((np.concatenate([ad, al, au]).astype(float),
                          (r, c)), shape=(n, n))


@pytest.mark.parametrize("dims", [(8, 8, 8), (5, 4, 3)])
def test_stencil_27_matches_scipy_built_stencil(dims):
    cell = small_cell("hpcg27.cg")
    cfg = dict(cell.config, nx=dims[0], ny=dims[1], nz=dims[2])
    n, ia, ja, ad, al, au = cell.config_mod.arrays(cfg)
    A = full_of(n, ia, ja, ad, al, au)
    assert abs(A - cell.config_mod.reference(cfg)).max() == 0.0
    # CSRC layout: strictly lower, ascending columns within each row
    rows = np.repeat(np.arange(n), np.diff(ia))
    assert np.all(ja < rows)
    assert np.all(np.diff(rows * n + ja) > 0)
    # per dimension of m points the stencil reaches 3m - 2 pairs
    assert A.nnz == np.prod([3 * m - 2 for m in dims])
    assert np.diff(A.indptr).max() == 27


def test_fem_tables_match_geometry():
    cell = small_cell("fem_tet.step")
    cfg, mod = cell.config, cell.config_mod
    coords, conn, etype = mod.mesh(cfg)
    lap, mass = mod.element_tables(cfg)
    kappa = mod.kappa_ring(cfg, np.random.default_rng(1), 2)
    ref = mod.reference(cfg, coords, conn, kappa[1])
    ke = kappa[1][:, None, None] * lap[etype] + mass * np.eye(4)
    rows = np.repeat(conn, 4, axis=1).ravel()
    cols = np.tile(conn, (1, 4)).ravel()
    A = sp.csr_matrix((ke.ravel().astype(float), (rows, cols)),
                      shape=ref.shape)
    assert abs(A - ref).max() == 0.0
    assert np.linalg.eigvalsh(ref.toarray()).min() > 0
    # every element positively oriented, 6 per cube
    e = coords[conn[:, 1:]] - coords[conn[:, :1]]
    assert np.all(np.linalg.det(e) > 0)
    assert conn.shape[0] == 6 * cfg["nx"] * cfg["ny"] * cfg["nz"]
