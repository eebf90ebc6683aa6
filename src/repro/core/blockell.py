"""Block-ELL packing of a CSRC matrix for the Pallas TPU kernel.

This is the hardware-adaptation layer (docs/DESIGN.md §4).  The paper's per-thread
row ranges become per-*tile* row ranges; the paper's "effective range" of a
thread becomes the tile's **window** — a contiguous slice of x/y that covers
every column the tile touches.  Windows are uniform-width and end-aligned to
the tile's last row, so the window start is an affine function of the tile id
(no scalar prefetch needed in the kernel):

    window(b) = [ (b+1)·TM - W,  (b+1)·TM )       (original coordinates)

W = round_up(TM + bandwidth, 128).  This holds because CSRC stores only the
lower triangle: every stored column j of row i satisfies i - band <= j <= i.

Slots are padded per row-tile to a common count S (multiple of the k-step),
ELL-style.  Padded slots carry value 0 and the sentinel column W (one-hot of
an out-of-range index is the zero vector — padding is numerically inert).

Layout (NT = ceil(n / TM) row tiles, S slots per tile):

    vals_l     (NT, S)  f32   lower values (diag excluded)
    vals_u     (NT, S)  f32   aligned upper values (absent if numerically sym.)
    col_local  (NT, S)  i32   j - win_lo(b)   in [0, W)   (W = padding sentinel)
    row_in_win (NT, S)  i32   i - win_lo(b)   in [W-TM, W)
    ad         (NT, TM) f32   diagonal, row-tiled

x is padded with W zeros on the left and to NT·TM on the right, so window b
in padded coordinates starts at (b+1)·TM.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from .csrc import CSRC, bandwidth, row_of_slot


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class BlockEll:
    n: int
    tm: int
    nt: int
    w_pad: int
    s: int                      # padded slots per tile
    vals_l: jnp.ndarray         # (NT, S)
    vals_u: jnp.ndarray         # (NT, S)  (== vals_l when num_symmetric)
    col_local: jnp.ndarray      # (NT, S)
    row_in_win: jnp.ndarray     # (NT, S)
    ad: jnp.ndarray             # (NT, TM)
    num_symmetric: bool
    pad_ratio: float            # NT*S / k  (ELL padding overhead; 1.0 = none)

    @property
    def n_pad(self) -> int:
        return self.nt * self.tm

    def streamed_bytes(self) -> int:
        """Bytes the kernel streams from HBM per product (the §Roofline
        memory term for the kernel): values + indices + x + y windows."""
        b = self.vals_l.size * self.vals_l.dtype.itemsize
        if not self.num_symmetric:
            b += self.vals_u.size * self.vals_u.dtype.itemsize
        b += self.col_local.size * self.col_local.dtype.itemsize
        b += self.row_in_win.size * self.row_in_win.dtype.itemsize
        b += self.ad.size * self.ad.dtype.itemsize
        b += (self.n_pad + self.w_pad) * 4          # x (windows overlap-read)
        b += self.nt * self.w_pad * 4               # window partials out
        return b


def pack(M: CSRC, tm: int = 128, k_step: int = 1024,
         w_cap: int = 4096, dtype=jnp.float32,
         index_dtype=jnp.int32) -> BlockEll:
    """Pack a square CSRC matrix into block-ELL tiles.

    Raises ValueError when the matrix band is too wide for the windowed
    kernel (w_pad would exceed ``w_cap``) — callers fall back to the
    segment-sum path (ref.csrc_spmv), mirroring the paper's finding that
    unbanded matrices (cage15, F1) defeat locality-based strategies.

    ``index_dtype=jnp.int16`` halves the index stream (local window
    offsets always fit: w_pad <= w_cap << 32767) — the paper's 16-bit
    index compression (§1, Williams et al.) applied at tile scope.
    """
    assert M.is_square, "block-ELL packs the square CSRC part only"
    n = M.n
    band = bandwidth(M)
    # multiple of 128 (lane alignment) AND of tm (overlap-add group size)
    w_pad = _round_up(tm + band, max(128, tm))
    if index_dtype == jnp.int16 and w_pad + 1 > 32767:
        raise ValueError(f"window {w_pad} overflows int16 indices")
    if w_pad > w_cap:
        raise ValueError(
            f"bandwidth {band} needs window {w_pad} > cap {w_cap}; "
            "use the segment-sum path")
    nt = max(1, -(-n // tm))
    ros = row_of_slot(M)
    ja = np.asarray(M.ja)
    al = np.asarray(M.al)
    au = np.asarray(M.au)
    tile_of_slot = ros // tm
    counts = np.bincount(tile_of_slot, minlength=nt)
    s = max(k_step, _round_up(int(counts.max()) if counts.size else k_step,
                              k_step))

    vals_l = np.zeros((nt, s), dtype=np.float32)
    vals_u = np.zeros((nt, s), dtype=np.float32)
    col_local = np.full((nt, s), w_pad, dtype=np.int32)       # sentinel
    row_in_win = np.full((nt, s), w_pad - 1, dtype=np.int32)  # inert
    # slots are row-major, so each tile's slots are consecutive: the
    # position in the tile is the offset from the tile's first slot
    first = np.searchsorted(tile_of_slot, np.arange(nt))
    pos_in_tile = np.arange(tile_of_slot.size) - first[tile_of_slot]
    win_lo = (np.arange(nt) + 1) * tm - w_pad                 # original coords
    t_idx = tile_of_slot
    p_idx = pos_in_tile
    vals_l[t_idx, p_idx] = al
    vals_u[t_idx, p_idx] = au
    col_local[t_idx, p_idx] = ja - win_lo[t_idx]
    row_in_win[t_idx, p_idx] = ros - win_lo[t_idx]

    ad = np.zeros((nt, tm), dtype=np.float32)
    ad.reshape(-1)[:n] = np.asarray(M.ad)

    k = max(1, int(ja.shape[0]))
    return BlockEll(
        n=n, tm=tm, nt=nt, w_pad=w_pad, s=s,
        vals_l=jnp.asarray(vals_l, dtype=dtype),
        vals_u=jnp.asarray(vals_l if M.numerically_symmetric else vals_u,
                           dtype=dtype),
        col_local=jnp.asarray(col_local, dtype=index_dtype),
        row_in_win=jnp.asarray(row_in_win, dtype=index_dtype),
        ad=jnp.asarray(ad, dtype=dtype),
        num_symmetric=bool(M.numerically_symmetric),
        pad_ratio=float(nt * s) / k,
    )


def refresh_values(pack_: BlockEll, M: CSRC) -> BlockEll:
    """Refill a pack's value streams (vals_l/vals_u/ad) from a matrix with
    **identical structure** — the FEM time-stepping fast path: no window
    recomputation, no index-stream rebuild, no per-slot Python loop.

    The slot→(tile, position) map is re-derived vectorized from ``ia``
    alone: slots are row-major, so within a tile they are consecutive and
    the position is ``slot_index − first_slot_of_tile``.  This reproduces
    the original pack's fill order exactly (the pack's stable-sort loop
    over a non-decreasing tile array is the identity order).
    """
    assert M.is_square and M.n == pack_.n, "structure mismatch"
    if bool(M.numerically_symmetric) != pack_.num_symmetric:
        raise ValueError(
            "numeric symmetry changed; the pack layout streams vals_u "
            "conditionally — rebuild instead of refreshing")
    ros = row_of_slot(M)
    k = ros.shape[0]
    tile = ros // pack_.tm
    first = np.searchsorted(tile, np.arange(pack_.nt))
    pos = np.arange(k) - first[tile]
    vals_l = np.zeros((pack_.nt, pack_.s), dtype=np.float32)
    vals_l[tile, pos] = np.asarray(M.al)
    if pack_.num_symmetric:          # vals_u aliases vals_l; skip the fill
        vals_u = vals_l
    else:
        vals_u = np.zeros((pack_.nt, pack_.s), dtype=np.float32)
        vals_u[tile, pos] = np.asarray(M.au)
    ad = np.zeros((pack_.nt, pack_.tm), dtype=np.float32)
    ad.reshape(-1)[:pack_.n] = np.asarray(M.ad)
    vdtype = pack_.vals_l.dtype
    return dataclasses.replace(
        pack_,
        vals_l=jnp.asarray(vals_l, dtype=vdtype),
        vals_u=jnp.asarray(vals_u, dtype=vdtype),
        ad=jnp.asarray(ad, dtype=pack_.ad.dtype))


def pad_x(pack_: BlockEll, x: jnp.ndarray) -> jnp.ndarray:
    """Left-pad by W and right-pad to NT*TM (window coordinates)."""
    return jnp.pad(x, (pack_.w_pad, pack_.n_pad - pack_.n))


def overlap_add(pack_: BlockEll, wins: jnp.ndarray) -> jnp.ndarray:
    """Accumulate per-tile windows into y — the paper's *effective*
    accumulation step, vectorized as overlap-add (hop TM, frame W).

    Windows are decomposed into r = W/TM groups of stride-r tiles; windows
    inside one group are disjoint, so each group reduces to a reshape +
    static-offset add (no scatter in the HLO).
    """
    nt, w = wins.shape
    tm = pack_.tm
    r = w // tm                      # W is a multiple of 128; ensure tm | w
    assert w % tm == 0, "w_pad must be a multiple of tm for overlap-add"
    y = jnp.zeros((pack_.w_pad + pack_.n_pad + w,), wins.dtype)
    for g in range(r):
        group = wins[g::r]                       # (ceil((nt-g)/r), W)
        ng = group.shape[0]
        if ng == 0:
            continue
        flat = group.reshape(ng * w)
        # window b starts (padded coords) at (b+1)*tm; group g holds tiles
        # b = g, g+r, g+2r, ... whose windows are back-to-back (stride r*tm = w)
        start = (g + 1) * tm
        y = jax.lax.dynamic_update_slice(
            y, jax.lax.dynamic_slice(y, (start,), (ng * w,)) + flat, (start,))
    return y[pack_.w_pad:pack_.w_pad + pack_.n]


def overlap_add_mm(pack_, wins: jnp.ndarray) -> jnp.ndarray:
    """Multi-RHS overlap-add: windows (NT, W, B) -> y (n, B).  Same group
    decomposition as :func:`overlap_add`, per RHS column.  Works for any
    pack exposing ``tm``/``w_pad``/``n_pad``/``n`` (rectangular BlockEll
    and the flat-grid FlatBlockEll share it)."""
    nt, w, nrhs = wins.shape
    tm = pack_.tm
    r = w // tm
    assert w % tm == 0, "w_pad must be a multiple of tm for overlap-add"
    y = jnp.zeros((pack_.w_pad + pack_.n_pad + w, nrhs), wins.dtype)
    for g in range(r):
        group = wins[g::r]
        ng = group.shape[0]
        if ng == 0:
            continue
        flat = group.reshape(ng * w, nrhs)
        start = (g + 1) * tm
        y = jax.lax.dynamic_update_slice(
            y, jax.lax.dynamic_slice(y, (start, 0), (ng * w, nrhs)) + flat,
            (start, 0))
    return y[pack_.w_pad:pack_.w_pad + pack_.n]
