"""Row-padded CSRC product (the 'ell' path): the row's own term as a dense
reduction, the transpose term as the one scatter-add left.

Of the CSRC product's two halves (docs/DESIGN.md §1) only the transpose
term ``y[ja[p]] += au[p]·x[i]`` writes rows other than the slot's own, so
only it can race and needs the paper's local-buffer accumulation.  The
row's own term ``y[i] += al[p]·x[ja[p]]`` has no conflict.  The segment
path treats both alike: it gathers ``x[row_of_slot]`` (a repeat of ``x``)
and scatter-adds by ``row_of_slot`` (sorted by construction).

Layout.  The lower slots of each row are padded to the widest row's
count ``W`` and stored slot-major, rows on lanes: ``ja`` / ``al`` / ``au``
of shape ``(W, n)``, plane ``w`` holding the ``w``-th slot of every row.
A padding entry points at its own row with value 0, so every index is in
range and no sentinel bin is needed.  ``au`` is left out of a numerically
symmetric matrix (the paper's one-fewer-load).  Then

    y  = ad·x + Σ_w al[w]·x[ja[w]]                  one gather, dense sum
    y += segment_sum(au·x[None, :], ja, n)          broadcast, one scatter

``plane_of_slot`` maps each CSRC slot to its flat position ``w·n + i`` in
the planes; a value refresh (FEM time stepping) re-pads ``al`` / ``au``
through it on the device, with the same shapes, so nothing recompiles.

The padded slot count ``n·W`` is what the layout costs over CSRC's ``k``;
``core/paths.py`` proposes the path only where it stays under
``ELL_PAD_MAX``·k.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csrc import CSRC


@dataclasses.dataclass(frozen=True)
class EllPack:
    """The slot-major row-padded planes of a square CSRC matrix."""
    n: int
    width: int                    # W: the most lower slots of any row
    ja: jnp.ndarray               # (W, n) int32; padding: the row itself
    al: jnp.ndarray               # (W, n); padding: 0
    au: Optional[jnp.ndarray]     # (W, n), or None (numerically symmetric)
    plane_of_slot: jnp.ndarray    # (k,) int32: w·n + i of each CSRC slot


def pack_ell(M: CSRC) -> EllPack:
    """Build the planes of a square matrix on the host (once per
    structure)."""
    n = M.n
    ia = np.asarray(M.ia, dtype=np.int64)
    counts = np.diff(ia)
    width = int(counts.max(initial=0))
    if n * width >= 2 ** 31:
        raise ValueError(f"ell planes of {n}x{width} overflow int32")
    rows = np.repeat(np.arange(n, dtype=np.int64), counts)
    pos = np.arange(rows.shape[0], dtype=np.int64) - ia[rows]
    plane = (pos * n + rows).astype(np.int32)
    ja = np.tile(np.arange(n, dtype=np.int32), width)
    ja[plane] = np.asarray(M.ja, dtype=np.int32)

    def planes(v):
        out = np.zeros(n * width, dtype=np.asarray(v).dtype)
        out[plane] = np.asarray(v)
        return jnp.asarray(out.reshape(width, n))

    return EllPack(
        n=n, width=width, ja=jnp.asarray(ja.reshape(width, n)),
        al=planes(M.al),
        au=None if M.numerically_symmetric else planes(M.au),
        plane_of_slot=jnp.asarray(plane))


@functools.partial(jax.jit, static_argnames=("width", "n"))
def _to_planes(plane_of_slot, v, *, width: int, n: int):
    out = jnp.zeros((width * n,), v.dtype)
    return out.at[plane_of_slot].set(v, unique_indices=True).reshape(width, n)


def refresh_ell_values(pack: EllPack, M: CSRC) -> EllPack:
    """The same planes with ``M``'s values, re-padded on the device."""
    def planes(v):
        return _to_planes(pack.plane_of_slot, jnp.asarray(v),
                          width=pack.width, n=pack.n)
    return dataclasses.replace(
        pack, al=planes(M.al),
        au=None if M.numerically_symmetric else planes(M.au))


def ell_spmv(pack: EllPack, ad, x):
    """y = A·x for x of shape (n,)."""
    upper = pack.al if pack.au is None else pack.au
    y = ad * x + jnp.sum(pack.al * x[pack.ja], axis=0)
    return y + jax.ops.segment_sum((upper * x[None, :]).reshape(-1),
                                   pack.ja.reshape(-1), num_segments=pack.n)


def ell_spmm(pack: EllPack, ad, X):
    """Y = A·X for X of shape (n, r)."""
    upper = pack.al if pack.au is None else pack.au
    r = X.shape[1]
    y = ad[:, None] * X + jnp.sum(pack.al[:, :, None] * X[pack.ja], axis=0)
    return y + jax.ops.segment_sum(
        (upper[:, :, None] * X[None]).reshape(-1, r),
        pack.ja.reshape(-1), num_segments=pack.n)
