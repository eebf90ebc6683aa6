"""Streaming executors for the block-ELL / flat / nnz-split CSRC products.

The one-hot Pallas kernels realize gather/scatter as (S, W) one-hot MXU
contractions — O(W) work per slot, so they are compute-bound.  The paper's
whole premise is that CSRC SpMV is *memory-bound*: per slot the kernel
must stream 12-16 bytes (value + local index [+ transpose value]) and do
O(1) arithmetic.  This module is that streaming formulation, selected by
``ExecutionPlan.variant == 'stream'``: the same per-tile-window
computation as the one-hot kernels, evaluated as one fused XLA expression
over all (tile, slot) pairs — one gather + one segment-sum per product
term, then the unchanged ``overlap_add`` accumulation.  It runs as XLA on
every backend: Mosaic lowers neither a 1-D gather nor a scatter-add, so
the streaming form has no in-grid Pallas body.

It computes the per-tile windows defined by the one-hot kernels — the
same slots summed into the same window positions — so for dyadic values
the results are bit-identical to them (the order of float additions is
the only difference; tests/test_stream_variant.py asserts equality).

Sentinel discipline (shared with the packers): padded slots carry value 0
and column sentinel ``w_pad``; the fused gather clamps the sentinel into
range (0 · x = 0) and the fused scatter maps it to segment id NT·W, one
past the last real segment, so ``segment_sum`` drops it — never an add
into a neighboring tile's window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.blockell import BlockEll, pad_x, overlap_add, overlap_add_mm
from repro.kernels.csrc_spmv_flat import FlatBlockEll
from repro.kernels.csrc_spmv_nnzsplit import NnzSplitPack


# ---------------------------------------------------------------------------
# Windowed packs (rect + flat share the window geometry)
# ---------------------------------------------------------------------------

def _windowed_indices(tile, cols, rows, *, tm: int, w_pad: int, nt: int):
    """Global padded-x gather indices and per-tile segment ids.

    ``tile`` is the row tile of each slot row ((G, 1) int32 — a trivial
    iota for the rectangular grid, ``tile_of_step`` for the flat grid);
    ``cols``/``rows`` are the (G, S) window-local index streams.  The x
    window of tile b starts at padded coordinate (b+1)·tm, so global
    gather index = (b+1)·tm + local; window segment id = b·W + local with
    the column sentinel (== W) routed to the drop segment NT·W.
    """
    xbase = (tile + 1) * tm
    segbase = tile * w_pad
    gcols = (xbase + cols).reshape(-1)
    grows = (xbase + rows).reshape(-1)
    seg_rows = (segbase + rows).reshape(-1)
    seg_cols = jnp.where(cols >= w_pad, nt * w_pad,
                         segbase + cols).reshape(-1)
    return gcols, grows, seg_rows, seg_cols


def _windowed_product(x_full, vl, vu, gcols, grows, seg_rows, seg_cols,
                      *, nt: int, w_pad: int):
    """The fused streaming core: two gathers, two segment-sums, (NT, W)."""
    limit = x_full.shape[0] - 1
    if x_full.ndim == 2:
        xg = jnp.take(x_full, jnp.minimum(gcols, limit), axis=0)
        xi = jnp.take(x_full, grows, axis=0)
        c_rows = (vl[:, None] * xg).astype(jnp.float32)
        c_cols = (vu[:, None] * xi).astype(jnp.float32)
    else:
        xg = jnp.take(x_full, jnp.minimum(gcols, limit))
        xi = jnp.take(x_full, grows)
        c_rows = (vl * xg).astype(jnp.float32)
        c_cols = (vu * xi).astype(jnp.float32)
    wins = jax.ops.segment_sum(c_rows, seg_rows, num_segments=nt * w_pad)
    wins = wins + jax.ops.segment_sum(c_cols, seg_cols,
                                      num_segments=nt * w_pad)
    return wins.reshape((nt, w_pad) + x_full.shape[1:])


def _diag_windows(ad, x_full, *, nt: int, tm: int, w_pad: int):
    xt = x_full[w_pad:w_pad + nt * tm]
    if x_full.ndim == 2:
        diag = ad.astype(jnp.float32).reshape(nt, tm)[..., None] * \
            xt.reshape(nt, tm, -1)
        return jnp.pad(diag, ((0, 0), (w_pad - tm, 0), (0, 0)))
    diag = ad.astype(jnp.float32).reshape(nt, tm) * xt.reshape(nt, tm)
    return jnp.pad(diag, ((0, 0), (w_pad - tm, 0)))


def _rect_streams(pack: BlockEll):
    nt, s = pack.vals_l.shape
    tile = jnp.arange(nt, dtype=jnp.int32)[:, None]
    cols = pack.col_local.astype(jnp.int32)
    rows = pack.row_in_win.astype(jnp.int32)
    vl = pack.vals_l.reshape(-1)
    vu = vl if pack.num_symmetric else pack.vals_u.reshape(-1)
    idx = _windowed_indices(tile, cols, rows, tm=pack.tm,
                            w_pad=pack.w_pad, nt=nt)
    return nt, vl, vu, idx


def blockell_spmv_stream(pack: BlockEll, x: jnp.ndarray) -> jnp.ndarray:
    nt, vl, vu, idx = _rect_streams(pack)
    x_full = pad_x(pack, x.astype(jnp.float32))
    wins = _windowed_product(x_full, vl, vu, *idx, nt=nt, w_pad=pack.w_pad)
    wins = wins + _diag_windows(pack.ad, x_full, nt=nt, tm=pack.tm,
                                w_pad=pack.w_pad)
    return overlap_add(pack, wins)


def blockell_spmm_stream(pack: BlockEll, X: jnp.ndarray) -> jnp.ndarray:
    assert X.shape[0] == pack.n
    nt, vl, vu, idx = _rect_streams(pack)
    x_full = jnp.pad(X.astype(jnp.float32),
                     ((pack.w_pad, pack.n_pad - pack.n), (0, 0)))
    wins = _windowed_product(x_full, vl, vu, *idx, nt=nt, w_pad=pack.w_pad)
    wins = wins + _diag_windows(pack.ad, x_full, nt=nt, tm=pack.tm,
                                w_pad=pack.w_pad)
    return overlap_add_mm(pack, wins)


def _flat_streams(pack: FlatBlockEll):
    total = pack.total_steps
    s0 = pack.ks * 128
    tile = pack.tile_of_step.astype(jnp.int32)[:, None]
    cols = pack.col_local.reshape(total, s0).astype(jnp.int32)
    rows = pack.row_in_win.reshape(total, s0).astype(jnp.int32)
    vl = pack.vals_l.reshape(-1)
    vu = vl if pack.num_symmetric else pack.vals_u.reshape(-1)
    idx = _windowed_indices(tile, cols, rows, tm=pack.tm,
                            w_pad=pack.w_pad, nt=pack.nt)
    return vl, vu, idx


def flat_spmv_stream(pack: FlatBlockEll, x: jnp.ndarray) -> jnp.ndarray:
    vl, vu, idx = _flat_streams(pack)
    x_full = jnp.pad(x.astype(jnp.float32),
                     (pack.w_pad, pack.n_pad - pack.n))
    wins = _windowed_product(x_full, vl, vu, *idx, nt=pack.nt,
                             w_pad=pack.w_pad)
    wins = wins + _diag_windows(pack.ad, x_full, nt=pack.nt, tm=pack.tm,
                                w_pad=pack.w_pad)
    return overlap_add(pack, wins)


def flat_spmm_stream(pack: FlatBlockEll, X: jnp.ndarray) -> jnp.ndarray:
    assert X.shape[0] == pack.n
    vl, vu, idx = _flat_streams(pack)
    x_full = jnp.pad(X.astype(jnp.float32),
                     ((pack.w_pad, pack.n_pad - pack.n), (0, 0)))
    wins = _windowed_product(x_full, vl, vu, *idx, nt=pack.nt,
                             w_pad=pack.w_pad)
    wins = wins + _diag_windows(pack.ad, x_full, nt=pack.nt, tm=pack.tm,
                                w_pad=pack.w_pad)
    return overlap_add_mm(pack, wins)


# ---------------------------------------------------------------------------
# Nnz-split chunks
# ---------------------------------------------------------------------------

def _chunk_segments(pack: NnzSplitPack):
    nc = pack.num_chunks
    seg = (jnp.arange(nc, dtype=jnp.int32)[:, None] * pack.r_pad
           + pack.lrow.reshape(nc, pack.s).astype(jnp.int32)).reshape(-1)
    return seg


def nnzsplit_spmv_stream(pack: NnzSplitPack, x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    xg = x[pack.src.astype(jnp.int32)]
    c = (pack.vals.reshape(-1).astype(jnp.float32) * xg)
    partial = jax.ops.segment_sum(
        c, _chunk_segments(pack),
        num_segments=pack.num_chunks * pack.r_pad)
    y_pad = jnp.zeros(pack.n + pack.r_pad, jnp.float32
                      ).at[pack.fixup_idx].add(partial)
    return y_pad[:pack.n] + pack.ad.astype(jnp.float32) * x


def nnzsplit_spmm_stream(pack: NnzSplitPack, X: jnp.ndarray) -> jnp.ndarray:
    n, nrhs = X.shape
    assert n == pack.n
    X = X.astype(jnp.float32)
    xg = X[pack.src.astype(jnp.int32), :]
    c = pack.vals.reshape(-1, 1).astype(jnp.float32) * xg
    partial = jax.ops.segment_sum(
        c, _chunk_segments(pack),
        num_segments=pack.num_chunks * pack.r_pad)
    y_pad = jnp.zeros((pack.n + pack.r_pad, nrhs), jnp.float32
                      ).at[pack.fixup_idx].add(partial)
    return y_pad[:pack.n] + pack.ad.astype(jnp.float32)[:, None] * X
