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

On a mesh (the path's ShardSupport, core/distributed.py) each shard holds
the same planes over its row block, stacked on a leading shard axis:
``EllHalo`` in the local coordinates of the halo strategy's ``x_ext``,
``EllShards`` in global columns for allreduce / reduce_scatter.  The
shard's product is the same gather, dense sum and one scatter-add, into
its ``y_ext`` or a length-n partial; the collectives are the strategy's.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csrc import CSRC, bandwidth


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


# ---------------------------------------------------------------------------
# Shard layouts for the distributed strategies (core/distributed.py through
# the path's ShardSupport): the same planes per row block of a mesh
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EllHalo:
    """Per-shard planes in *local* halo coordinates (the 'halo' strategy):
    shard t owns the ``ns`` rows ``[t·ns, (t+1)·ns)``; its columns index
    ``x_ext``, the rows ``[t·ns − h, (t+1)·ns)``, so row ``i`` of the shard
    is ``h + i`` there.  A padding entry points at its own row, value 0."""
    p: int
    ns: int                       # rows per shard (8-aligned)
    h: int                        # halo width (8-aligned bandwidth)
    n_local: int                  # ns + h
    width: int                    # W: the most lower slots of any row
    ja: jnp.ndarray               # (p, W, ns) int32, in [0, ns + h)
    al: jnp.ndarray               # (p, W, ns)
    au: Optional[jnp.ndarray]     # (p, W, ns), or None (numerically symmetric)
    ad: jnp.ndarray               # (p, ns)
    plane_of_slot: jnp.ndarray    # (k,) int32: (t·W + w)·ns + i of each slot


@dataclasses.dataclass(frozen=True)
class EllShards:
    """Per-shard planes over a row partition in *global* columns (the
    'allreduce' / 'reduce_scatter' strategies): shard t owns the rows
    ``[row0[t], row0[t+1])``, at most ``ns``; its product is a full-length
    partial y."""
    p: int
    n: int
    ns: int                       # the most rows of any shard (8-aligned)
    width: int
    row0: jnp.ndarray             # (p,) int32: each shard's first row
    ja: jnp.ndarray               # (p, W, ns) int32, in [0, n)
    al: jnp.ndarray
    au: Optional[jnp.ndarray]
    ad: jnp.ndarray               # (p, ns)
    plane_of_slot: jnp.ndarray    # (k,) int32


def _shard_of_rows(starts: np.ndarray, rows: np.ndarray) -> np.ndarray:
    return np.searchsorted(starts, rows, side="right") - 1


def _round8(v: int) -> int:
    return (v + 7) // 8 * 8


def _ad_planes(ad, starts: np.ndarray, ns: int) -> jnp.ndarray:
    """The diagonal in the shards' row blocks, (p, ns), 0 past each."""
    p = starts.shape[0] - 1
    rows = np.arange(starts[-1], dtype=np.int64)
    t = _shard_of_rows(starts, rows)
    out = np.zeros(p * ns, np.float32)
    out[t * ns + rows - starts[t]] = np.asarray(ad, np.float32)
    return jnp.asarray(out.reshape(p, ns))


def _pack_blocks(M: CSRC, starts: np.ndarray, ns: int,
                 col_base: np.ndarray) -> dict:
    """The planes of the row blocks ``[starts[t], starts[t+1])``, ``ns``
    rows each, shard t's columns shifted down by ``col_base[t]``.  Refuses
    a block whose planes pad more than ``ELL_PAD_MAX`` times its lower
    slots."""
    from repro.core.paths import ELL_PAD_MAX, ell_padding_ok
    n, p = M.n, starts.shape[0] - 1
    ia = np.asarray(M.ia, dtype=np.int64)
    counts = np.diff(ia)
    width = int(counts.max(initial=0))
    if p * width * ns >= 2 ** 31:
        raise ValueError(f"ell shard planes of {p}x{width}x{ns} overflow "
                         "int32")
    rows = np.repeat(np.arange(n, dtype=np.int64), counts)
    t = _shard_of_rows(starts, rows)
    k_of = np.bincount(t, minlength=p)
    for s in range(p):
        if not ell_padding_ok(int(starts[s + 1] - starts[s]), width,
                              int(k_of[s])):
            raise ValueError(
                f"ell planes of shard {s} pad more than {ELL_PAD_MAX}x "
                f"its {int(k_of[s])} lower slots")
    pos = np.arange(rows.shape[0], dtype=np.int64) - ia[rows]
    plane = ((t * width + pos) * ns + rows - starts[t]).astype(np.int32)
    # padding: each plane entry points at its own row (the last row for
    # the rows past n), so every index is in range
    own = np.minimum(starts[:-1, None] + np.arange(ns), max(n - 1, 0))
    ja = np.broadcast_to((own - col_base[:, None])[:, None, :],
                         (p, width, ns)).astype(np.int32).reshape(-1)
    ja[plane] = np.asarray(M.ja, dtype=np.int64) - col_base[t]

    def planes(v):
        out = np.zeros(p * width * ns, dtype=np.asarray(v).dtype)
        out[plane] = np.asarray(v)
        return jnp.asarray(out.reshape(p, width, ns))

    return dict(
        p=p, ns=ns, width=width, ja=jnp.asarray(ja.reshape(p, width, ns)),
        al=planes(M.al),
        au=None if M.numerically_symmetric else planes(M.au),
        ad=_ad_planes(M.ad, starts, ns), plane_of_slot=jnp.asarray(plane))


def _halo_starts(n: int, p: int, ns: int) -> np.ndarray:
    return np.minimum(np.arange(p + 1, dtype=np.int64) * ns, n)


def pack_ell_halo(M: CSRC, p: int) -> EllHalo:
    """The halo strategy's per-shard planes.  Raises ValueError when the
    band does not fit inside one shard (the gate of
    ``schedule.build_halo_layout``) or a shard's planes break the
    padding gate."""
    assert M.is_square
    n = M.n
    ns = _round8(-(-n // p))
    band = bandwidth(M)
    h = max(8, _round8(band))
    if h > ns:
        raise ValueError(
            f"band {band} exceeds shard rows {ns}; halo strategy needs "
            "band <= n/p (fall back to allreduce/reduce_scatter)")
    starts = _halo_starts(n, p, ns)
    return EllHalo(h=h, n_local=ns + h,
                   **_pack_blocks(M, starts, ns, starts[:-1] - h))


def pack_ell_shards(M: CSRC, starts) -> EllShards:
    """The allreduce / reduce_scatter strategies' per-shard planes over the
    row partition ``starts`` ((p+1,) boundaries from the schedule layer)."""
    assert M.is_square
    starts = np.asarray(starts, dtype=np.int64)
    ns = _round8(max(1, int(np.diff(starts).max(initial=0))))
    return EllShards(
        n=M.n, row0=jnp.asarray(starts[:-1].astype(np.int32)),
        **_pack_blocks(M, starts, ns, np.zeros(starts.shape[0] - 1,
                                                np.int64)))


def _refresh_blocks(lay, M: CSRC, starts: np.ndarray):
    """The same planes with ``M``'s values, re-padded on the device through
    ``plane_of_slot``; index planes and shapes are kept."""
    def planes(v):
        return _to_planes(lay.plane_of_slot, jnp.asarray(v),
                          width=lay.p * lay.width, n=lay.ns
                          ).reshape(lay.p, lay.width, lay.ns)
    return dataclasses.replace(
        lay, al=planes(M.al),
        au=None if M.numerically_symmetric else planes(M.au),
        ad=_ad_planes(M.ad, starts, lay.ns))


def refresh_ell_halo(lay: EllHalo, M: CSRC) -> EllHalo:
    return _refresh_blocks(lay, M, _halo_starts(M.n, lay.p, lay.ns))


def refresh_ell_shards(lay: EllShards, M: CSRC, starts) -> EllShards:
    return _refresh_blocks(lay, M, np.asarray(starts, dtype=np.int64))


# --- shard_map plumbing (ShardSupport hooks) -------------------------------

def ell_shard_arrays(lay):
    """Leading-axis-p arrays a shard_map local function consumes."""
    head = (lay.row0,) if isinstance(lay, EllShards) else ()
    upper = () if lay.au is None else (lay.au,)
    return head + (lay.ja, lay.al) + upper + (lay.ad,)


def _own_rows(ja, al, ad, x_src, x_own):
    """ad·x_own + Σ_w al[w]·x_src[ja[w]]: one gather, a dense reduction."""
    if x_own.ndim == 2:
        return ad[:, None] * x_own + jnp.sum(al[:, :, None] * x_src[ja],
                                             axis=0)
    return ad * x_own + jnp.sum(al * x_src[ja], axis=0)


def _transpose_rows(ja, upper, x_own, num: int):
    """segment_sum(upper·x_own, ja): the one scatter-add."""
    if x_own.ndim == 2:
        v = (upper[:, :, None] * x_own[None]).reshape(-1, x_own.shape[1])
    else:
        v = (upper * x_own[None, :]).reshape(-1)
    return jax.ops.segment_sum(v, ja.reshape(-1), num_segments=num)


def ell_local_fn(lay, n_local: int):
    """Shard-local row-padded product over the shard_map slices (leading
    axis 1), for x of shape (·,) or (·, r).  On an ``EllHalo`` x is the
    shard's ``x_ext`` and the result its ``y_ext`` (``n_local`` rows); on
    an ``EllShards`` x is the whole replicated vector and the result the
    shard's length-n partial.  Plain XLA: no interpret mode, no variant."""
    ns = lay.ns
    symmetric = lay.au is None

    def split(arrays):
        ja, al, *rest = arrays
        ad = rest[-1][0]
        upper = al[0] if symmetric else rest[0][0]
        return ja[0], al[0], upper, ad

    if isinstance(lay, EllHalo):
        h = lay.h

        def local_halo(*args):
            ja, al, upper, ad = split(args[:-1])
            x_ext = args[-1]
            x_own = x_ext[h:]
            y = _transpose_rows(ja, upper, x_own, n_local)
            return y.at[h:].add(_own_rows(ja, al, ad, x_ext, x_own))

        return local_halo

    def local_shards(row0, *args):
        ja, al, upper, ad = split(args[:-1])
        x = args[-1]
        tail = ((0, 0),) * (x.ndim - 1)
        r0 = row0[0]
        x_own = jax.lax.dynamic_slice_in_dim(
            jnp.pad(x, ((0, ns),) + tail), r0, ns)
        own = _own_rows(ja, al, ad, x, x_own)
        y = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((n_local + ns,) + x.shape[1:], own.dtype), own, r0, 0)
        return y[:n_local] + _transpose_rows(ja, upper, x_own, n_local)

    return local_shards


def ell_halo_dims(lay: EllHalo):
    return lay.ns, lay.h, lay.n_local
