"""P1 tetrahedral heat conduction on a structured Kuhn mesh: the inputs
the program assembles and solves, and their plain reference.

The box of ``nx*ny*nz`` cubes (edge ``spacing``) is cut into 6 tetrahedra
per cube, one per monotone lattice path from corner 000 to corner 111;
node ``(ix, iy, iz)`` is number ``ix + (nx+1)*(iy + (ny+1)*iz)``.  Every
tetrahedron of one path is a translate of the others, so the Laplacians
come from a table of 6 element matrices (``element_tables``).

Element ``e`` in time step ``t`` contributes ``kappa[t, e] * L_e +
(mass * V_e / 4) * I``: the P1 Laplacian scaled by a conductivity drawn
from ``kappa_choices``, plus the lumped mass (backward Euler with the step
folded into kappa).  Entries are rounded to multiples of ``1/quantum``,
so with dyadic kappa the float32 sums are exact in any order.

``reference`` assembles one step's global matrix in float64 from element
geometry with scipy, without the type table.
"""
from __future__ import annotations

import itertools

import numpy as np


def _node(cfg, ix, iy, iz):
    return ix + (cfg["nx"] + 1) * (iy + (cfg["ny"] + 1) * iz)


def mesh(cfg: dict):
    """``(coords, conn, etype)``: node coordinates (num_nodes, 3) float64,
    connectivity (ne, 4) int32 with positive orientation, and each
    element's path number (ne,) int8."""
    nx, ny, nz, h = cfg["nx"], cfg["ny"], cfg["nz"], float(cfg["spacing"])
    iz, iy, ix = np.meshgrid(np.arange(nz + 1), np.arange(ny + 1),
                             np.arange(nx + 1), indexing="ij")
    coords = h * np.stack([ix.ravel(), iy.ravel(), iz.ravel()],
                          axis=1).astype(np.float64)
    cz, cy, cx = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                             indexing="ij")
    cx, cy, cz = cx.ravel(), cy.ravel(), cz.ravel()
    origin = _node(cfg, cx, cy, cz)
    step = (_node(cfg, 1, 0, 0), _node(cfg, 0, 1, 0), _node(cfg, 0, 0, 1))
    conn, etype = [], []
    for t, perm in enumerate(itertools.permutations((0, 1, 2))):
        v1 = origin + step[perm[0]]
        v2 = v1 + step[perm[1]]
        v3 = v2 + step[perm[2]]
        # every element of one path has the orientation of the first
        if np.linalg.det(coords[[v1[0], v2[0], v3[0]]]
                         - coords[origin[0]]) < 0:
            v2, v3 = v3, v2
        conn.append(np.stack([origin, v1, v2, v3], axis=1))
        etype.append(np.full(origin.shape[0], t, np.int8))
    return (coords, np.concatenate(conn).astype(np.int32),
            np.concatenate(etype))


def _quantize(a, quantum):
    return np.round(np.asarray(a, np.float64) * quantum) / quantum


def _p1_laplacians(pts: np.ndarray) -> np.ndarray:
    """P1 Laplacians of tetrahedra with vertices ``pts`` (ne, 4, 3):
    ``V * grad(phi_a) . grad(phi_b)``, float64."""
    edges = pts[:, 1:] - pts[:, :1]
    inv = np.linalg.inv(edges)                    # columns: dual basis
    grads = np.concatenate([-inv.sum(axis=2)[:, None, :],
                            inv.transpose(0, 2, 1)], axis=1)
    vol = np.abs(np.linalg.det(edges)) / 6.0
    return vol[:, None, None] * np.einsum("ead,ebd->eab", grads, grads)


def _lumped_mass_entry(cfg) -> float:
    h = float(cfg["spacing"])
    return float(_quantize(cfg["mass"] * h ** 3 / 6.0 / 4.0,
                           cfg["quantum"]))


def element_tables(cfg: dict):
    """``(lap, mass)``: the 6 quantized element Laplacians (6, 4, 4)
    float32, one per path number, and the quantized lumped-mass diagonal
    entry of every element."""
    coords, conn, etype = mesh(dict(cfg, nx=1, ny=1, nz=1))
    lap = _quantize(_p1_laplacians(coords[conn]), cfg["quantum"])
    table = np.zeros((6, 4, 4))
    table[etype] = lap
    return table.astype(np.float32), _lumped_mass_entry(cfg)


def kappa_ring(cfg: dict, rng: np.random.Generator, slots: int):
    """Conductivities (slots, ne) float32, one row per time step of the
    ring, drawn from ``kappa_choices``."""
    ne = 6 * cfg["nx"] * cfg["ny"] * cfg["nz"]
    choices = np.asarray(cfg["kappa_choices"], np.float32)
    return choices[rng.integers(0, choices.size, (slots, ne))]


def lumped_mass(cfg: dict, conn: np.ndarray, num_nodes: int) -> np.ndarray:
    """Diagonal of the assembled lumped mass matrix (num_nodes,)."""
    m = np.bincount(conn.ravel(), minlength=num_nodes)
    return (m * _lumped_mass_entry(cfg)).astype(np.float64)


def reference(cfg: dict, coords, conn, kappa):
    """The float64 scipy CSR matrix of one step with conductivities
    ``kappa`` (ne,), from element geometry."""
    import scipy.sparse as sp
    lap = _quantize(_p1_laplacians(coords[conn]), cfg["quantum"])
    ke = np.asarray(kappa, np.float64)[:, None, None] * lap
    ke[:, np.arange(4), np.arange(4)] += _lumped_mass_entry(cfg)
    rows = np.repeat(conn, 4, axis=1).ravel()
    cols = np.tile(conn, (1, 4)).ravel()
    n = coords.shape[0]
    return sp.coo_matrix((ke.ravel(), (rows, cols)), shape=(n, n)).tocsr()
