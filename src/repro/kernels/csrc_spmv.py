"""Pallas TPU kernel for the block-ELL CSRC sparse matrix-vector product.

TPU adaptation of the paper's parallel CSRC SpMV (docs/DESIGN.md §4):

  * a grid program = one (row-tile b, k-step kt) pair — the paper's "thread
    processing a row range" at VMEM-tile granularity;
  * the scatter `y[ja] += au·x[i]` and gather `y[i] += al·x[ja]` terms are
    both realized as **one-hot MXU matmuls** against the tile's x-window —
    TPUs have no atomics or efficient per-lane scatter, so indexing becomes
    arithmetic.  One-hot of the padding sentinel (index == W) is the zero
    vector, so ELL padding is numerically inert;
  * each program accumulates into a per-tile output *window* (the paper's
    "local buffer" restricted to its "effective range"); windows are
    combined by `core.blockell.overlap_add` — the *effective accumulation*
    step, expressed as reshape+add (scatter-free HLO);
  * for numerically symmetric matrices only `vals_l` is streamed (the
    paper's one-fewer-load optimization — here it saves 4 of ~16 streamed
    bytes/slot, directly visible in the memory roofline term).

One body serves SpMV and SpMM: x is a (B, ·) row block (B = 1 for SpMV),
so every operand keeps slots on lanes and the block shapes satisfy the
Mosaic tiling rule (last two dims full or divisible by 8×128):

    slot streams   (NT, NK·KS, 128), block (·, KS, 128)
    x windows      (NT, B, W) gathered in XLA before the call, block (·, B, W)
    output windows (NT, B, W), revisited across the k-step axis

The diagonal term and the window gather are plain XLA around the call.
The flat-grid kernel (csrc_spmv_flat.py) shares ``onehot_step`` and the
window helpers; tests/test_tpu_compile.py compiles both for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blockell import BlockEll, overlap_add, overlap_add_mm
from repro.runtime import interpret_mode

# Widest x window the one-hot body fits in the default scoped VMEM of a
# v5e: the two (W, 128) masks of every sublane row stay live across the
# unrolled k-step (W = 4096 asks ~28 MiB against the 16 MiB limit).
ONEHOT_MAX_WINDOW = 2048

HIGHEST = jax.lax.Precision.HIGHEST
# (B, L) x (W, L) -> (B, W): contract the lane dim of both operands
LANE_CONTRACT = (((1,), (1,)), ((), ()))


def onehot_step(vals_l_ref, vals_u_ref, col_ref, row_ref, xw, *,
                w_pad: int, num_symmetric: bool) -> jnp.ndarray:
    """(B, W) window partial of one (KS, 128) k-step block.

    Each sublane row k builds two (W, 128) masks by comparing its column
    and row offsets with a sublane iota, gathers x through them
    (xw @ mask -> (B, 128)), scales by the slot values, and scatters back
    through the same masks contracted on their lane dim.  Contractions
    run at HIGHEST precision: the masks are exact, the products stay
    float32.  Index and value streams may be 16-bit; they are widened
    in-register.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, (w_pad, 128), 0)
    win = jnp.zeros((xw.shape[0], w_pad), jnp.float32)
    for k in range(col_ref.shape[0]):
        row = pl.ds(k, 1)
        oh_c = (iota == col_ref[row, :].astype(jnp.int32)).astype(jnp.float32)
        oh_r = (iota == row_ref[row, :].astype(jnp.int32)).astype(jnp.float32)
        vl = vals_l_ref[row, :].astype(jnp.float32)              # (1, 128)
        vu = vl if num_symmetric else vals_u_ref[row, :].astype(jnp.float32)
        xg = jnp.dot(xw, oh_c, precision=HIGHEST,
                     preferred_element_type=jnp.float32)         # x[ja[p]]
        xi = jnp.dot(xw, oh_r, precision=HIGHEST,
                     preferred_element_type=jnp.float32)         # x[i]
        win += jax.lax.dot_general(vl * xg, oh_r, LANE_CONTRACT,
                                   precision=HIGHEST,
                                   preferred_element_type=jnp.float32)
        win += jax.lax.dot_general(vu * xi, oh_c, LANE_CONTRACT,
                                   precision=HIGHEST,
                                   preferred_element_type=jnp.float32)
    return win


def x_windows(pack, X: jnp.ndarray) -> jnp.ndarray:
    """(NT, B, W) x windows of a windowed pack for X (n, B).

    Window b covers padded coordinates [(b+1)·TM, (b+1)·TM + W), i.e. the
    W/TM row tiles b+1 .. b+W/TM of x left-padded by W — a concatenation
    of W/TM shifted slices, no gather."""
    nt, tm, w = pack.nt, pack.tm, pack.w_pad
    r = w // tm
    xt = jnp.pad(X.astype(jnp.float32).T, ((0, 0), (w, nt * tm - pack.n)))
    tiles = xt.reshape(X.shape[1], r + nt, tm)
    win = jnp.concatenate([tiles[:, 1 + j:1 + j + nt] for j in range(r)],
                          axis=2)
    return jnp.swapaxes(win, 0, 1)


def with_diagonal(pack, wins: jnp.ndarray, xw: jnp.ndarray) -> jnp.ndarray:
    """Add the diagonal term to (NT, B, W) windows: tile b's own rows are
    the last TM entries of its window."""
    tm = pack.tm
    d = pack.ad.astype(jnp.float32)[:, None, :] * xw[:, :, -tm:]
    return wins + jnp.pad(d, ((0, 0), (0, 0), (pack.w_pad - tm, 0)))


def accumulate(pack, wins: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Overlap-add (NT, B, W) windows into y of x's rank."""
    if x.ndim == 1:
        return overlap_add(pack, wins[:, 0, :])
    return overlap_add_mm(pack, jnp.swapaxes(wins, 1, 2))


def _kernel(vals_l_ref, vals_u_ref, col_ref, row_ref, x_ref, out_ref, *,
            w_pad: int, num_symmetric: bool):
    win = onehot_step(vals_l_ref, vals_u_ref, col_ref, row_ref, x_ref[...],
                      w_pad=w_pad, num_symmetric=num_symmetric)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = win

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        out_ref[...] += win


def blockell_windows(pack: BlockEll, X: jnp.ndarray,
                     k_step_sublanes: int = 8,
                     interpret=None) -> jnp.ndarray:
    """Per-tile (NT, B, W) windows of A·X for X (n, B), diagonal included,
    before the overlap-add."""
    nt, s = pack.vals_l.shape
    ks = k_step_sublanes
    assert s % (ks * 128) == 0, "slot count must divide the k-step"
    nk = s // (ks * 128)
    xw = x_windows(pack, X)
    nrhs = X.shape[1]

    def slots(a):
        return a.reshape(nt, nk * ks, 128)

    slot_spec = pl.BlockSpec((None, ks, 128), lambda b, kt: (b, kt, 0))
    win_spec = pl.BlockSpec((None, nrhs, pack.w_pad), lambda b, kt: (b, 0, 0))
    wins = pl.pallas_call(
        functools.partial(_kernel, w_pad=pack.w_pad,
                          num_symmetric=pack.num_symmetric),
        grid=(nt, nk),
        in_specs=[slot_spec] * 4 + [win_spec],
        out_specs=win_spec,
        out_shape=jax.ShapeDtypeStruct((nt, nrhs, pack.w_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(slots(pack.vals_l), slots(pack.vals_u), slots(pack.col_local),
      slots(pack.row_in_win), xw)
    return with_diagonal(pack, wins, xw)


def blockell_spmv_windows(pack: BlockEll, x: jnp.ndarray,
                          k_step_sublanes: int = 8,
                          interpret=None) -> jnp.ndarray:
    """SpMV windows (NT, W) before accumulation."""
    return blockell_windows(pack, x[:, None], k_step_sublanes,
                            interpret)[:, 0, :]


def blockell_spmv(pack: BlockEll, x: jnp.ndarray, interpret=None,
                  k_step_sublanes: int = 8) -> jnp.ndarray:
    """Full product: kernel windows + effective accumulation."""
    return accumulate(pack, blockell_windows(pack, x[:, None],
                                             k_step_sublanes, interpret), x)


def blockell_spmm(pack: BlockEll, X: jnp.ndarray, k_step_sublanes: int = 8,
                  interpret=None) -> jnp.ndarray:
    """Y = A @ X for X (n, B); the one-hot contractions become genuine MXU
    matmuls, so arithmetic intensity rises with B."""
    assert X.shape[0] == pack.n
    return accumulate(pack, blockell_windows(pack, X, k_step_sublanes,
                                             interpret), X)
