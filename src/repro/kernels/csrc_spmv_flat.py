"""Flattened-grid variant of the block-ELL CSRC SpMV/SpMM kernels.

The rectangular (NT, NK) grid of csrc_spmv.py pads every row tile to the
slot count of the densest tile — skewed matrices waste bandwidth on ELL
padding (pad_ratio).  Here each row tile gets only the k-steps it needs:

  * slots are packed flat as (total_ksteps, KS, 128);
  * the grid is 1-D over k-steps; each program learns its row tile from a
    scalar-prefetched ``tile_of_step`` array (pltpu.PrefetchScalarGridSpec
    — the index maps consume the prefetch ref);
  * programs of one row tile are consecutive, so the revisited-output
    window accumulation works exactly as in the rectangular kernel, with
    "first step of my tile" read from a second prefetched flag array.

Cross-tile padding drops from (max_b nk_b)·NT to Σ_b nk_b k-steps — on a
skewed FEM matrix this is the difference between pad_ratio ~3 and ~1.1
(see tests/test_flat_path.py and docs/DESIGN.md §4).

The flat path is a first-class registered KernelPath (core/paths.py):
tuner-enumerable on skewed matrices, schedule-cached (`FlatBlockEll` is
the npz-serialized artifact), and executable shard-locally inside every
distributed accumulation strategy via the stacked per-shard layouts at
the bottom of this module (``FlatShards`` for allreduce/reduce_scatter,
``FlatHalo`` for the effective/halo strategy).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.csrc import CSRC, bandwidth, row_of_slot
from repro.core.blockell import _round_up
from repro.kernels.csrc_spmv import (accumulate, onehot_step, with_diagonal,
                                     x_windows)
from repro.runtime import interpret_mode


@dataclasses.dataclass(frozen=True)
class FlatBlockEll:
    n: int
    tm: int
    nt: int
    w_pad: int
    total_steps: int            # Σ_b nk_b  (k-steps overall)
    ks: int                     # sublanes per k-step
    vals_l: jnp.ndarray         # (total, KS, 128)
    vals_u: jnp.ndarray
    col_local: jnp.ndarray      # (total, KS, 128)
    row_in_win: jnp.ndarray
    ad: jnp.ndarray             # (NT, TM)
    tile_of_step: jnp.ndarray   # (total,) int32 — row tile of each k-step
    first_of_tile: jnp.ndarray  # (total,) int32 — 1 on a tile's first step
    num_symmetric: bool
    pad_ratio: float

    @property
    def n_pad(self) -> int:
        return self.nt * self.tm

    def streamed_bytes(self) -> int:
        b = self.vals_l.size * self.vals_l.dtype.itemsize
        if not self.num_symmetric:
            b += self.vals_u.size * self.vals_u.dtype.itemsize
        b += self.col_local.size * self.col_local.dtype.itemsize
        b += self.row_in_win.size * self.row_in_win.dtype.itemsize
        b += self.ad.size * self.ad.dtype.itemsize
        b += (self.n_pad + self.w_pad) * 4
        b += self.nt * self.w_pad * 4
        return b


def _flat_arrays(ros, ja, al, au, *, nt: int, tm: int, w_pad: int,
                 step: int, pad_steps_to: Optional[int] = None):
    """Fill the flat step arrays for one slot set (rows/cols may be global
    or shard-local coordinates — the packer only assumes every slot's
    column lies inside its tile's window).

    Every tile gets at least one k-step so its output window is always
    initialized (the kernel's first-of-tile write).  ``pad_steps_to``
    appends inert trailing steps (zero values, sentinel indices, assigned
    to the last tile so tile programs stay consecutive) — used to equalize
    per-shard step counts for the stacked distributed layouts.
    """
    tile_of_slot = ros // tm
    counts = np.bincount(tile_of_slot, minlength=nt)
    nk = np.maximum(1, -(-counts // step))          # k-steps per tile
    total = int(nk.sum())
    steps = total if pad_steps_to is None else int(pad_steps_to)
    if steps < total:
        raise ValueError(f"pad_steps_to {steps} < required steps {total}")

    vals_l = np.zeros((steps, step), np.float32)
    vals_u = np.zeros((steps, step), np.float32)
    col_local = np.full((steps, step), w_pad, np.int32)
    row_in_win = np.full((steps, step), w_pad - 1, np.int32)
    tile_of_step = np.full(steps, nt - 1, np.int32)
    tile_of_step[:total] = np.repeat(np.arange(nt, dtype=np.int32), nk)
    first = np.zeros(steps, np.int32)
    starts = np.concatenate([[0], np.cumsum(nk)])[:-1]
    first[starts] = 1

    win_lo = (np.arange(nt) + 1) * tm - w_pad
    # stable tile order; q = a slot's position within its tile
    order = np.argsort(tile_of_slot, kind="stable")
    t = tile_of_slot[order]
    q = np.arange(t.size) - np.searchsorted(t, t)
    j = starts[t] + q // step
    pos = q % step
    vals_l[j, pos] = al[order]
    vals_u[j, pos] = au[order]
    col_local[j, pos] = ja[order] - win_lo[t]
    row_in_win[j, pos] = ros[order] - win_lo[t]
    return vals_l, vals_u, col_local, row_in_win, tile_of_step, first, total


def pack_flat(M: CSRC, tm: int = 128, ks: int = 8, w_cap: int = 4096,
              dtype=jnp.float32, index_dtype=jnp.int32) -> FlatBlockEll:
    """Per-tile-exact packing (no cross-tile ELL padding).

    ``dtype=jnp.bfloat16`` halves the value streams (plan.value_dtype);
    ``index_dtype=jnp.int16`` halves the index streams (plan.index_dtype).
    """
    assert M.is_square
    n = M.n
    band = bandwidth(M)
    w_pad = _round_up(tm + band, max(128, tm))
    if index_dtype == jnp.int16 and w_pad + 1 > 32767:
        raise ValueError(f"window {w_pad} overflows int16 indices")
    if w_pad > w_cap:
        raise ValueError(f"window {w_pad} > cap {w_cap}")
    nt = max(1, -(-n // tm))
    step = ks * 128
    (vals_l, vals_u, col_local, row_in_win, tile_of_step, first,
     total) = _flat_arrays(row_of_slot(M), np.asarray(M.ja),
                           np.asarray(M.al), np.asarray(M.au),
                           nt=nt, tm=tm, w_pad=w_pad, step=step)

    ad = np.zeros((nt, tm), np.float32)
    ad.reshape(-1)[:n] = np.asarray(M.ad)
    k = max(1, int(np.asarray(M.ja).shape[0]))
    return FlatBlockEll(
        n=n, tm=tm, nt=nt, w_pad=w_pad, total_steps=total, ks=ks,
        vals_l=jnp.asarray(vals_l.reshape(total, ks, 128), dtype=dtype),
        vals_u=jnp.asarray((vals_l if M.numerically_symmetric else vals_u
                            ).reshape(total, ks, 128), dtype=dtype),
        col_local=jnp.asarray(col_local.reshape(total, ks, 128),
                              dtype=index_dtype),
        row_in_win=jnp.asarray(row_in_win.reshape(total, ks, 128),
                               dtype=index_dtype),
        ad=jnp.asarray(ad, dtype=dtype),
        tile_of_step=jnp.asarray(tile_of_step),
        first_of_tile=jnp.asarray(first),
        num_symmetric=bool(M.numerically_symmetric),
        pad_ratio=float(total * step) / k,
    )


def refresh_flat_values(pack: FlatBlockEll, M: CSRC) -> FlatBlockEll:
    """Refill a flat pack's value streams from a same-structure matrix
    (FEM time stepping): the step/position map is re-derived vectorized
    from the row pointers — identical to the original fill order (the
    packer's stable sort over a non-decreasing tile array is the identity)
    — and no index stream or tile map is touched."""
    assert M.is_square and M.n == pack.n, "structure mismatch"
    if bool(M.numerically_symmetric) != pack.num_symmetric:
        raise ValueError(
            "numeric symmetry changed; rebuild instead of refreshing")
    step = pack.ks * 128
    vals_l, vals_u = _value_fill_steps(
        row_of_slot(M), np.asarray(M.al), np.asarray(M.au),
        nt=pack.nt, tm=pack.tm, step=step, steps=pack.total_steps,
        num_symmetric=pack.num_symmetric)
    ad = np.zeros((pack.nt, pack.tm), np.float32)
    ad.reshape(-1)[:pack.n] = np.asarray(M.ad)
    vdtype = pack.vals_l.dtype
    return dataclasses.replace(
        pack,
        vals_l=jnp.asarray(vals_l.reshape(pack.total_steps, pack.ks, 128),
                           dtype=vdtype),
        vals_u=jnp.asarray(vals_u.reshape(pack.total_steps, pack.ks, 128),
                           dtype=vdtype),
        ad=jnp.asarray(ad, dtype=pack.ad.dtype))


def _kernel(tile_ref, first_ref, vals_l_ref, vals_u_ref, col_ref, row_ref,
            x_ref, out_ref, *, w_pad: int, num_symmetric: bool):
    j = pl.program_id(0)
    win = onehot_step(vals_l_ref, vals_u_ref, col_ref, row_ref, x_ref[...],
                      w_pad=w_pad, num_symmetric=num_symmetric)

    @pl.when(first_ref[j] == 1)
    def _init():
        out_ref[...] = win

    @pl.when(first_ref[j] != 1)
    def _acc():
        out_ref[...] += win


def flat_windows(pack: FlatBlockEll, X: jnp.ndarray,
                 interpret=None) -> jnp.ndarray:
    """Per-tile (NT, B, W) windows of A·X for X (n, B), diagonal included:
    the rectangular kernel's body over the flat step list, each step's
    x/output window picked by its prefetched tile id."""
    xw = x_windows(pack, X)
    nrhs = X.shape[1]
    slot_spec = pl.BlockSpec((None, pack.ks, 128),
                             lambda j, tile, first: (j, 0, 0))
    win_spec = pl.BlockSpec((None, nrhs, pack.w_pad),
                            lambda j, tile, first: (tile[j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pack.total_steps,),
        in_specs=[slot_spec] * 4 + [win_spec],
        out_specs=win_spec,
    )
    wins = pl.pallas_call(
        functools.partial(_kernel, w_pad=pack.w_pad,
                          num_symmetric=pack.num_symmetric),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((pack.nt, nrhs, pack.w_pad),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(interpret),
    )(pack.tile_of_step, pack.first_of_tile,
      pack.vals_l, pack.vals_u, pack.col_local, pack.row_in_win, xw)
    return with_diagonal(pack, wins, xw)


def flat_spmv(pack: FlatBlockEll, x: jnp.ndarray,
              interpret=None) -> jnp.ndarray:
    return accumulate(pack, flat_windows(pack, x[:, None], interpret), x)


def flat_spmm(pack: FlatBlockEll, X: jnp.ndarray,
              interpret=None) -> jnp.ndarray:
    """Y = A @ X for X (n, B) — the multi-RHS flat-grid product (batched
    serving / block-Krylov shape) with the same per-tile-exact step layout
    as flat_spmv."""
    assert X.shape[0] == pack.n
    return accumulate(pack, flat_windows(pack, X, interpret), X)


# ---------------------------------------------------------------------------
# Shard-local flat layouts for the distributed strategies
# (consumed through core/schedule.py's memoized builders)
# ---------------------------------------------------------------------------

def _stack_shard_packs(slot_sets, *, nt, tm, w_pad, step, num_symmetric,
                       dtype=jnp.float32, index_dtype=jnp.int32):
    """Build one flat pack per shard and stack on a leading shard axis.

    ``slot_sets`` yields (ros, ja, al, au) per shard.  Step counts are
    equalized across shards (shard_map needs uniform shapes) by padding to
    the widest shard with inert steps.
    """
    per_tile = []
    for ros, ja, al, au in slot_sets:
        counts = np.bincount(ros // tm, minlength=nt)
        per_tile.append(int(np.maximum(1, -(-counts // step)).sum()))
    steps = max(per_tile)
    ks = step // 128
    out = {k: [] for k in ("vals_l", "vals_u", "col_local", "row_in_win",
                           "tile_of_step", "first_of_tile")}
    for ros, ja, al, au in slot_sets:
        (vl, vu, cl, rw, tos, first, _total) = _flat_arrays(
            ros, ja, al, au, nt=nt, tm=tm, w_pad=w_pad, step=step,
            pad_steps_to=steps)
        out["vals_l"].append(vl.reshape(steps, ks, 128))
        out["vals_u"].append((vl if num_symmetric else vu
                              ).reshape(steps, ks, 128))
        out["col_local"].append(cl.reshape(steps, ks, 128))
        out["row_in_win"].append(rw.reshape(steps, ks, 128))
        out["tile_of_step"].append(tos)
        out["first_of_tile"].append(first)
    arrays = {}
    for k, v in out.items():
        dt = (index_dtype if k in ("col_local", "row_in_win")
              else dtype if k in ("vals_l", "vals_u") else None)
        arrays[k] = jnp.asarray(np.stack(v), dtype=dt)
    return steps, arrays


@dataclasses.dataclass(frozen=True)
class FlatShards:
    """Per-shard flat sub-packs of one matrix in *global* coordinates
    (allreduce / reduce_scatter strategies): shard t's pack holds only the
    slots of its partition rows, plus its slice of the diagonal; running
    the flat kernel over it yields the shard's full-length partial y."""
    p: int
    n: int
    tm: int
    nt: int
    w_pad: int
    steps: int                  # uniform k-steps per shard (padded)
    ks: int
    vals_l: jnp.ndarray         # (p, steps, KS, 128)
    vals_u: jnp.ndarray
    col_local: jnp.ndarray
    row_in_win: jnp.ndarray
    ad: jnp.ndarray             # (p, NT, TM) — shard-owned diagonal
    tile_of_step: jnp.ndarray   # (p, steps)
    first_of_tile: jnp.ndarray  # (p, steps)
    num_symmetric: bool

    def shard_pack(self, t: int) -> FlatBlockEll:
        """Shard t's pack as a standalone FlatBlockEll (also the shape the
        shard_map local function rebuilds from its slices)."""
        return FlatBlockEll(
            n=self.n, tm=self.tm, nt=self.nt, w_pad=self.w_pad,
            total_steps=self.steps, ks=self.ks,
            vals_l=self.vals_l[t], vals_u=self.vals_u[t],
            col_local=self.col_local[t], row_in_win=self.row_in_win[t],
            ad=self.ad[t], tile_of_step=self.tile_of_step[t],
            first_of_tile=self.first_of_tile[t],
            num_symmetric=self.num_symmetric, pad_ratio=1.0)


def pack_flat_shards(M: CSRC, starts, tm: int = 128, ks: int = 8,
                     w_cap: int = 4096, dtype=jnp.float32,
                     index_dtype=jnp.int32) -> FlatShards:
    """Split a square CSRC matrix into per-shard flat packs along the row
    partition ``starts`` ((p+1,) boundaries from the schedule layer)."""
    assert M.is_square
    n = M.n
    band = bandwidth(M)
    w_pad = _round_up(tm + band, max(128, tm))
    if index_dtype == jnp.int16 and w_pad + 1 > 32767:
        raise ValueError(f"window {w_pad} overflows int16 indices")
    if w_pad > w_cap:
        raise ValueError(f"window {w_pad} > cap {w_cap}")
    nt = max(1, -(-n // tm))
    step = ks * 128
    starts = np.asarray(starts, dtype=np.int64)
    p = starts.shape[0] - 1
    ros = row_of_slot(M)
    ja = np.asarray(M.ja)
    al = np.asarray(M.al)
    au = np.asarray(M.au)

    def slot_sets():
        for t in range(p):
            sel = (ros >= starts[t]) & (ros < starts[t + 1])
            yield ros[sel], ja[sel], al[sel], au[sel]

    steps, arrays = _stack_shard_packs(
        list(slot_sets()), nt=nt, tm=tm, w_pad=w_pad, step=step,
        num_symmetric=M.numerically_symmetric, dtype=dtype,
        index_dtype=index_dtype)

    ad = np.zeros((p, nt * tm), np.float32)
    ad_full = np.asarray(M.ad)
    for t in range(p):
        r0, r1 = int(starts[t]), int(starts[t + 1])
        ad[t, r0:r1] = ad_full[r0:r1]
    return FlatShards(
        p=p, n=n, tm=tm, nt=nt, w_pad=w_pad, steps=steps, ks=ks,
        ad=jnp.asarray(ad.reshape(p, nt, tm), dtype=dtype),
        num_symmetric=bool(M.numerically_symmetric), **arrays)


@dataclasses.dataclass(frozen=True)
class FlatHalo:
    """Per-shard flat packs in *local* halo coordinates (the paper's
    effective-accumulation strategy): shard t owns ns rows; its local
    matrix covers rows [r0-h, r1) of y, i.e. n_local = ns + h rows with
    the halo rows first — exactly the y_ext/x_ext layout of
    schedule.build_halo_layout, but executed by the flat kernel."""
    p: int
    ns: int                     # rows per shard (8-aligned)
    h: int                      # halo width (8-aligned bandwidth)
    n_local: int                # ns + h
    tm: int
    nt: int                     # local row tiles: ceil(n_local / tm)
    w_pad: int
    steps: int
    ks: int
    vals_l: jnp.ndarray         # (p, steps, KS, 128)
    vals_u: jnp.ndarray
    col_local: jnp.ndarray
    row_in_win: jnp.ndarray
    ad: jnp.ndarray             # (p, NT, TM) local-coordinate diagonal
    tile_of_step: jnp.ndarray
    first_of_tile: jnp.ndarray
    num_symmetric: bool

    def shard_pack(self, t: int) -> FlatBlockEll:
        return FlatBlockEll(
            n=self.n_local, tm=self.tm, nt=self.nt, w_pad=self.w_pad,
            total_steps=self.steps, ks=self.ks,
            vals_l=self.vals_l[t], vals_u=self.vals_u[t],
            col_local=self.col_local[t], row_in_win=self.row_in_win[t],
            ad=self.ad[t], tile_of_step=self.tile_of_step[t],
            first_of_tile=self.first_of_tile[t],
            num_symmetric=self.num_symmetric, pad_ratio=1.0)


def pack_flat_halo(M: CSRC, p: int, tm: int = 128, ks: int = 8,
                   w_cap: int = 4096, dtype=jnp.float32,
                   index_dtype=jnp.int32) -> FlatHalo:
    """Per-shard local flat packs for the halo strategy.  Raises ValueError
    when the band does not fit inside one shard (same feasibility gate as
    schedule.build_halo_layout) or the local window exceeds ``w_cap``."""
    assert M.is_square
    n = M.n
    ns = _round_up(-(-n // p), 8)
    band = bandwidth(M)
    h = max(8, _round_up(band, 8))
    if h > ns:
        raise ValueError(
            f"band {band} exceeds shard rows {ns}; halo strategy needs "
            "band <= n/p (fall back to allreduce/reduce_scatter)")
    n_local = ns + h
    # every local row i stores columns in [i-h, i]: bandwidth_local <= h
    w_pad = _round_up(tm + h, max(128, tm))
    if index_dtype == jnp.int16 and w_pad + 1 > 32767:
        raise ValueError(f"window {w_pad} overflows int16 indices")
    if w_pad > w_cap:
        raise ValueError(f"window {w_pad} > cap {w_cap}")
    nt = max(1, -(-n_local // tm))
    step = ks * 128

    ros = row_of_slot(M)
    ja = np.asarray(M.ja)
    al = np.asarray(M.al)
    au = np.asarray(M.au)
    shard_of_slot = ros // ns

    def slot_sets():
        for t in range(p):
            sel = shard_of_slot == t
            # local row r0+i -> h+i; column j -> j - (r0 - h)
            yield (ros[sel] - t * ns + h, ja[sel] - (t * ns - h),
                   al[sel], au[sel])

    steps, arrays = _stack_shard_packs(
        list(slot_sets()), nt=nt, tm=tm, w_pad=w_pad, step=step,
        num_symmetric=M.numerically_symmetric, dtype=dtype,
        index_dtype=index_dtype)

    ad = np.zeros((p, nt * tm), np.float32)
    ad_full = np.asarray(M.ad)
    for t in range(p):
        r0 = t * ns
        r1 = min(n, r0 + ns)
        if r1 > r0:
            ad[t, h:h + (r1 - r0)] = ad_full[r0:r1]
    return FlatHalo(
        p=p, ns=ns, h=h, n_local=n_local, tm=tm, nt=nt, w_pad=w_pad,
        steps=steps, ks=ks,
        ad=jnp.asarray(ad.reshape(p, nt, tm), dtype=dtype),
        num_symmetric=bool(M.numerically_symmetric), **arrays)


# ---------------------------------------------------------------------------
# Same-structure value refresh of the stacked shard layouts (the mesh-path
# analog of refresh_flat_values: FEM time stepping / serving update_values
# must not re-pack or re-partition on the mesh)
# ---------------------------------------------------------------------------

def _value_fill_steps(ros, al, au, *, nt, tm, step, steps, num_symmetric):
    """Vectorized value-only refill of one shard's flat step arrays.

    ``ros`` is the shard's slot rows (global or local coordinates),
    non-decreasing — exactly the order `_flat_arrays` filled with (its
    stable sort over a non-decreasing tile array is the identity), so the
    (step, position) map is re-derived without touching index streams.
    """
    k = ros.shape[0]
    vals_l = np.zeros((steps, step), np.float32)
    vals_u = vals_l if num_symmetric else np.zeros((steps, step), np.float32)
    if k:
        tile = ros // tm
        counts = np.bincount(tile, minlength=nt)
        nk = np.maximum(1, -(-counts // step))
        starts = np.concatenate([[0], np.cumsum(nk)])[:-1]
        first_slot = np.searchsorted(tile, np.arange(nt))
        q = np.arange(k) - first_slot[tile]
        j = starts[tile] + q // step
        pos = q % step
        vals_l[j, pos] = al
        if not num_symmetric:
            vals_u[j, pos] = au
    return vals_l, vals_u


def refresh_flat_shards(fs: FlatShards, M: CSRC, starts) -> FlatShards:
    """Refill a FlatShards stack's value streams from a same-structure
    matrix over the same partition ``starts`` — no index stream, tile map,
    or step-count work."""
    assert M.is_square and M.n == fs.n, "structure mismatch"
    if bool(M.numerically_symmetric) != fs.num_symmetric:
        raise ValueError(
            "numeric symmetry changed; rebuild instead of refreshing")
    starts = np.asarray(starts, dtype=np.int64)
    ros = row_of_slot(M)
    al = np.asarray(M.al)
    au = np.asarray(M.au)
    step = fs.ks * 128
    vls, vus = [], []
    for t in range(fs.p):
        sel = (ros >= starts[t]) & (ros < starts[t + 1])
        vl, vu = _value_fill_steps(
            ros[sel], al[sel], au[sel], nt=fs.nt, tm=fs.tm, step=step,
            steps=fs.steps, num_symmetric=fs.num_symmetric)
        vls.append(vl.reshape(fs.steps, fs.ks, 128))
        vus.append(vu.reshape(fs.steps, fs.ks, 128))
    ad = np.zeros((fs.p, fs.nt * fs.tm), np.float32)
    ad_full = np.asarray(M.ad)
    for t in range(fs.p):
        r0, r1 = int(starts[t]), int(starts[t + 1])
        ad[t, r0:r1] = ad_full[r0:r1]
    vdtype = fs.vals_l.dtype
    return dataclasses.replace(
        fs,
        vals_l=jnp.asarray(np.stack(vls), dtype=vdtype),
        vals_u=jnp.asarray(np.stack(vus), dtype=vdtype),
        ad=jnp.asarray(ad.reshape(fs.p, fs.nt, fs.tm),
                       dtype=fs.ad.dtype))


def refresh_flat_halo(lay: FlatHalo, M: CSRC) -> FlatHalo:
    """Refill a FlatHalo stack's value streams from a same-structure
    matrix (local halo coordinates re-derived from the layout geometry)."""
    assert M.is_square, "structure mismatch"
    if bool(M.numerically_symmetric) != lay.num_symmetric:
        raise ValueError(
            "numeric symmetry changed; rebuild instead of refreshing")
    n = M.n
    ros = row_of_slot(M)
    al = np.asarray(M.al)
    au = np.asarray(M.au)
    shard_of_slot = ros // lay.ns
    step = lay.ks * 128
    vls, vus = [], []
    for t in range(lay.p):
        sel = shard_of_slot == t
        vl, vu = _value_fill_steps(
            ros[sel] - t * lay.ns + lay.h, al[sel], au[sel],
            nt=lay.nt, tm=lay.tm, step=step, steps=lay.steps,
            num_symmetric=lay.num_symmetric)
        vls.append(vl.reshape(lay.steps, lay.ks, 128))
        vus.append(vu.reshape(lay.steps, lay.ks, 128))
    ad = np.zeros((lay.p, lay.nt * lay.tm), np.float32)
    ad_full = np.asarray(M.ad)
    for t in range(lay.p):
        r0 = t * lay.ns
        r1 = min(n, r0 + lay.ns)
        if r1 > r0:
            ad[t, lay.h:lay.h + (r1 - r0)] = ad_full[r0:r1]
    vdtype = lay.vals_l.dtype
    return dataclasses.replace(
        lay,
        vals_l=jnp.asarray(np.stack(vls), dtype=vdtype),
        vals_u=jnp.asarray(np.stack(vus), dtype=vdtype),
        ad=jnp.asarray(ad.reshape(lay.p, lay.nt, lay.tm),
                       dtype=lay.ad.dtype))


# --- shard_map plumbing (ShardSupport hooks) -------------------------------

def flat_shard_arrays(fs):
    """Leading-axis-p arrays a shard_map local function consumes."""
    return (fs.tile_of_step, fs.first_of_tile, fs.vals_l, fs.vals_u,
            fs.col_local, fs.row_in_win, fs.ad)


def flat_local_fn(fs, n_local: int, interpret=None, variant="onehot"):
    """Shard-local flat-grid product: rebuild the shard's FlatBlockEll from
    the shard_map-sliced stacked arrays and run the Pallas kernel (SpMV or
    SpMM by x rank).  ``fs`` is a FlatShards or FlatHalo layout."""
    def local_y(tile, first, vals_l, vals_u, col, row, ad, x):
        pk = FlatBlockEll(
            n=n_local, tm=fs.tm, nt=fs.nt, w_pad=fs.w_pad,
            total_steps=fs.steps, ks=fs.ks,
            vals_l=vals_l[0], vals_u=vals_u[0], col_local=col[0],
            row_in_win=row[0], ad=ad[0], tile_of_step=tile[0],
            first_of_tile=first[0],
            num_symmetric=fs.num_symmetric, pad_ratio=1.0)
        if variant == "stream":
            from repro.kernels import csrc_spmv_stream as stream_mod
            return (stream_mod.flat_spmm_stream(pk, x) if x.ndim == 2
                    else stream_mod.flat_spmv_stream(pk, x))
        if x.ndim == 2:
            return flat_spmm(pk, x, interpret=interpret)
        return flat_spmv(pk, x, interpret=interpret)

    return local_y


def flat_halo_dims(lay: FlatHalo):
    return lay.ns, lay.h, lay.n_local
