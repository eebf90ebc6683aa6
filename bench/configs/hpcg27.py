"""HPCG's 27-point stencil: the matrix the program runs, and its plain
reference.

Rows are grid points in lexicographic order, ``i = x + nx*(y + ny*z)``.
Each row couples to its up to 26 neighbours in the 3x3x3 box around it:
the diagonal holds ``diagonal`` (26), every present neighbour
``off_diagonal`` (-1).  Boundary rows have fewer neighbours, so the matrix
is strictly diagonally dominant there and symmetric positive definite.

``arrays`` builds the CSRC arrays (lower triangle, row-major, ascending
columns) with vectorized numpy.  ``rhs_ring`` makes right-hand sides
``b = A·x`` on the device, applying the stencil to the grid directly.
``reference`` builds the same matrix in float64 another way, as
``(diag+1)·I - kron(T, T, T)`` with ``T`` the tridiagonal matrix of
ones, and shares no code with ``arrays``.
"""
from __future__ import annotations

import numpy as np


def _lower_offsets(nx: int, ny: int):
    """The 13 neighbour offsets that point to a lower row, in ascending
    order of their linear offset (so columns come out ascending)."""
    offs = [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
            for dx in (-1, 0, 1) if dz * nx * ny + dy * nx + dx < 0]
    return sorted(offs, key=lambda o: o[2] * nx * ny + o[1] * nx + o[0])


def arrays(cfg: dict):
    """``(n, ia, ja, ad, al, au)``: the CSRC arrays of the stencil, with
    values in the configuration's dtype."""
    nx, ny, nz = int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"])
    dtype = np.dtype(cfg["dtype"])
    n = nx * ny * nz
    idx = np.arange(n, dtype=np.int64)
    x, y, z = idx % nx, (idx // nx) % ny, idx // (nx * ny)
    offs = _lower_offsets(nx, ny)
    cols = np.empty((n, len(offs)), np.int64)
    ok = np.empty((n, len(offs)), bool)
    for c, (dx, dy, dz) in enumerate(offs):
        ok[:, c] = ((0 <= x + dx) & (x + dx < nx) & (0 <= y + dy)
                    & (y + dy < ny) & (0 <= z + dz) & (z + dz < nz))
        cols[:, c] = idx + dz * nx * ny + dy * nx + dx
    ja = cols[ok].astype(np.int32)               # row-major, ascending
    ia = np.zeros(n + 1, np.int64)
    np.cumsum(ok.sum(axis=1), out=ia[1:])
    ad = np.full(n, cfg["diagonal"], dtype)
    al = np.full(ja.shape[0], cfg["off_diagonal"], dtype)
    return n, ia.astype(np.int32), ja, ad, al, al.copy()


def rhs_ring(cfg: dict, rng: np.random.Generator, ring: int):
    """``(ring, n)`` float32 right-hand sides ``b = A·x`` on the device,
    each ``x`` standard normal from ``rng``, in one jitted call."""
    import jax
    import jax.numpy as jnp
    nx, ny, nz = int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"])
    a = float(cfg["off_diagonal"])
    shift = float(cfg["diagonal"]) - a

    def make(key):
        x = jax.random.normal(key, (ring, nz, ny, nx), jnp.float32)
        box = x
        for axis in (1, 2, 3):      # 3x3x3 box sum, zero outside the grid
            pad = [(0, 0)] * 4
            pad[axis] = (1, 1)
            p = jnp.pad(box, pad)
            m = box.shape[axis]
            box = sum(jax.lax.slice_in_dim(p, o, o + m, axis=axis)
                      for o in range(3))
        return (shift * x + a * box).reshape(ring, nx * ny * nz)

    key = jax.random.key(int(rng.integers(0, 2 ** 31 - 1)))
    return jax.jit(make)(key)


def reference(cfg: dict):
    """The float64 scipy CSR matrix of the stencil."""
    import scipy.sparse as sp
    nx, ny, nz = int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"])

    def ones3(m):
        return sp.diags([np.ones(m - 1), np.ones(m), np.ones(m - 1)],
                        [-1, 0, 1], format="csr")

    box = sp.kron(sp.kron(ones3(nz), ones3(ny)), ones3(nx), format="csr")
    n = nx * ny * nz
    a = float(cfg["off_diagonal"])
    shift = float(cfg["diagonal"]) - a
    return (a * box + shift * sp.identity(n, format="csr")).tocsr()
