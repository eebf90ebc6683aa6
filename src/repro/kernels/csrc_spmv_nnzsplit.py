"""Nnz-split (merge-style) CSRC SpMV/SpMM kernels for unstructured matrices.

Every other registered path assumes band-ish structure: the windowed paths
(kernel, flat) pad a per-tile column window that explodes when ``ja``
spreads across the full index range, and row-based balancing loses when
the nnz-per-row distribution is heavy-tailed (power-law graphs: one hub
row can outweigh a thousand others).  This module is the CSRC analogue of
merge/nonzero-split CSR SpMV: work is balanced over *non-zeros*, not rows.

Layout.  The symmetric storage is first expanded into one combined
scatter stream of K = 2k entries — lower slot p at (i, j) contributes
(dest=i, src=j, val=al[p]) and its transpose partner (dest=j, src=i,
val=au[p]) — stably sorted by ``dest``.  The stream is cut into
equal-size chunks of S = ks·128 entries regardless of row boundaries
(rows may span chunks).  Each chunk c covers a contiguous row interval
starting at ``chunk_row0[c]``; per entry we store the chunk-local row
``lrow = dest - chunk_row0[c]`` (bounded by the chunk's row span, padded
to ``r_pad``) and the global gather index ``src``.

Execution.  ``x[src]`` is gathered outside the kernel (a single
contiguous stream read; unstructured matrices have no window to exploit,
so an in-kernel one-hot gather would be O(S·n)).  The Pallas grid is 1-D
over chunks; each program reduces its S products into an ``r_pad``-wide
partial row vector with one one-hot matmul per sublane row of the chunk
(MXU-friendly, no in-kernel scatter) and writes its own output row — no
cross-program accumulation, so no first-of-tile bookkeeping.  An XLA
fix-up pass scatter-adds
the per-chunk partials at ``chunk_row0[c] + r`` — rows split across a
chunk boundary are merged here — and the diagonal term closes the
product.  All float32 sums are plain adds, so for dyadic values the
result is bit-identical to any other summation order (the tests compare
against the dense oracle with assert_array_equal).

Shard layouts for the distributed strategies mirror the flat path's:
``NnzSplitShards`` keeps global coordinates and partitions the combined
stream by dest ownership (allreduce / reduce_scatter — each shard emits a
full-length partial y), ``NnzSplitHalo`` assigns both halves of a slot to
the shard owning its *row* and rebases coordinates into the local
[r0-h, r1) frame of the halo exchange.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.csrc import CSRC, bandwidth, row_of_slot
from repro.core.blockell import _round_up
from repro.kernels.csrc_spmv import HIGHEST, LANE_CONTRACT
from repro.runtime import interpret_mode


def _combined_stream(M: CSRC):
    """The dest-sorted scatter stream of the square symmetric part."""
    ros = row_of_slot(M).astype(np.int64)
    ja = np.asarray(M.ja, dtype=np.int64)
    dest = np.concatenate([ros, ja])
    src = np.concatenate([ja, ros])
    val = np.concatenate([np.asarray(M.al, dtype=np.float32),
                          np.asarray(M.au, dtype=np.float32)])
    order = np.argsort(dest, kind="stable")   # deterministic: value refresh
    return dest[order], src[order], val[order]   # re-derives the same order


def _chunk_arrays(dest, src, val, *, ks: int, num_chunks=None, r_pad=None):
    """Cut one dest-sorted stream into equal-S chunks.

    ``num_chunks`` / ``r_pad`` force the geometry (used to equalize shapes
    across shards); padding entries carry val=0 on the stream's last real
    row, so they add exact zeros.  Returns the per-chunk numpy arrays.
    """
    s = ks * 128
    kk = int(dest.shape[0])
    need = max(1, -(-kk // s))
    nc = need if num_chunks is None else int(num_chunks)
    if nc < need:
        raise ValueError(f"num_chunks {nc} < required {need}")
    pad = nc * s - kk
    fill_dest = int(dest[-1]) if kk else 0
    dest = np.concatenate([dest, np.full(pad, fill_dest, np.int64)])
    src = np.concatenate([src, np.zeros(pad, np.int64)])
    val = np.concatenate([val, np.zeros(pad, np.float32)])
    dest = dest.reshape(nc, s)
    chunk_row0 = dest[:, 0].copy()
    span = int((dest[:, -1] - chunk_row0).max()) + 1
    rp = _round_up(max(span, 1), 128) if r_pad is None else int(r_pad)
    if span > rp:
        raise ValueError(f"chunk row span {span} > r_pad {rp}")
    lrow = (dest - chunk_row0[:, None]).astype(np.int32)
    fixup = (chunk_row0[:, None]
             + np.arange(rp, dtype=np.int64)[None, :]).reshape(-1)
    return dict(num_chunks=nc, r_pad=rp,
                vals=val.reshape(nc, ks, 128),
                lrow=lrow.reshape(nc, ks, 128),
                src=src.reshape(-1).astype(np.int64),
                chunk_row0=chunk_row0.astype(np.int32),
                fixup_idx=fixup.astype(np.int32))


@dataclasses.dataclass(frozen=True)
class NnzSplitPack:
    n: int
    num_chunks: int
    ks: int                     # sublanes per chunk: S = ks*128 entries
    r_pad: int                  # per-chunk local row window (128-aligned)
    vals: jnp.ndarray           # (C, KS, 128) dest-sorted combined values
    lrow: jnp.ndarray           # (C, KS, 128) dest - chunk_row0[chunk]
    src: jnp.ndarray            # (C*S,) global gather index into x
    chunk_row0: jnp.ndarray     # (C,) first dest row of each chunk
    fixup_idx: jnp.ndarray      # (C*r_pad,) scatter rows into y_pad
    ad: jnp.ndarray             # (n,) diagonal
    num_symmetric: bool
    pad_ratio: float            # allocated slots / real stream entries

    @property
    def s(self) -> int:
        return self.ks * 128

    def streamed_bytes(self) -> int:
        b = self.vals.size * self.vals.dtype.itemsize
        b += self.lrow.size * self.lrow.dtype.itemsize
        b += self.src.size * self.src.dtype.itemsize
        b += self.src.size * 4                      # gathered x stream
        b += self.fixup_idx.size * self.fixup_idx.dtype.itemsize
        b += self.num_chunks * self.r_pad * 4       # partials written+read
        b += self.ad.size * self.ad.dtype.itemsize
        b += 2 * self.n * 4                         # x and y
        return b


def pack_nnzsplit(M: CSRC, ks: int = 8, r_cap: int = 4096,
                  dtype=jnp.float32, index_dtype=jnp.int32) -> NnzSplitPack:
    """Equal-nnz chunking of a square CSRC matrix.

    ``r_cap`` bounds the per-chunk row window: a stream whose chunks skip
    huge row gaps (near-diagonal matrices with a handful of scattered
    entries) would pad every chunk to the worst gap — those matrices
    belong to the banded paths, so the packer raises (same contract as the
    windowed packers' w_cap gate).
    """
    assert M.is_square
    n = M.n
    if index_dtype == jnp.int16 and n > 32767:
        raise ValueError(f"n {n} overflows int16 gather indices")
    dest, src, val = _combined_stream(M)
    ch = _chunk_arrays(dest, src, val, ks=ks)
    if ch["r_pad"] > r_cap:
        raise ValueError(f"chunk row window {ch['r_pad']} > cap {r_cap}")
    kk = max(1, int(dest.shape[0]))
    return NnzSplitPack(
        n=n, num_chunks=ch["num_chunks"], ks=ks, r_pad=ch["r_pad"],
        vals=jnp.asarray(ch["vals"], dtype=dtype),
        lrow=jnp.asarray(ch["lrow"], dtype=index_dtype),
        src=jnp.asarray(ch["src"], dtype=index_dtype),
        chunk_row0=jnp.asarray(ch["chunk_row0"]),
        fixup_idx=jnp.asarray(ch["fixup_idx"]),
        ad=jnp.asarray(np.asarray(M.ad), dtype=dtype),
        num_symmetric=bool(M.numerically_symmetric),
        pad_ratio=float(ch["num_chunks"] * ks * 128) / kk,
    )


def refresh_nnzsplit_values(pack: NnzSplitPack, M: CSRC) -> NnzSplitPack:
    """Refill the value stream from a same-structure matrix: the stable
    dest argsort is re-derived (structure unchanged means the same
    permutation), values refilled, no index stream touched."""
    assert M.is_square and M.n == pack.n, "structure mismatch"
    if bool(M.numerically_symmetric) != pack.num_symmetric:
        raise ValueError(
            "numeric symmetry changed; rebuild instead of refreshing")
    _dest, _src, val = _combined_stream(M)
    s = pack.ks * 128
    pad = pack.num_chunks * s - val.shape[0]
    if pad < 0:
        raise ValueError("structure mismatch: stream longer than pack")
    val = np.concatenate([val, np.zeros(pad, np.float32)])
    return dataclasses.replace(
        pack,
        vals=jnp.asarray(val.reshape(pack.num_chunks, pack.ks, 128),
                         dtype=pack.vals.dtype),
        ad=jnp.asarray(np.asarray(M.ad), dtype=pack.ad.dtype))


# ---------------------------------------------------------------------------
# Kernel: one program per chunk, one one-hot matmul per sublane row
# ---------------------------------------------------------------------------

def _kernel(vals_ref, lrow_ref, xg_ref, out_ref, *, r_pad: int):
    """(B, r_pad) partial of one chunk: each sublane row's (r_pad, 128)
    row mask, contracted on lanes with that row's products."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (r_pad, 128), 0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for k in range(lrow_ref.shape[0]):
        row = pl.ds(k, 1)
        oh = (iota == lrow_ref[row, :].astype(jnp.int32)).astype(jnp.float32)
        c = vals_ref[row, :].astype(jnp.float32) * xg_ref[k]       # (B, 128)
        acc += jax.lax.dot_general(c, oh, LANE_CONTRACT, precision=HIGHEST,
                                   preferred_element_type=jnp.float32)
    out_ref[...] = acc


def nnzsplit_spmm(pack: NnzSplitPack, X: jnp.ndarray,
                  interpret=None) -> jnp.ndarray:
    """Y = A @ X for X (n, B).  x[src] is gathered in XLA and laid out
    (C, KS, B, 128) so every block keeps stream entries on lanes."""
    n, nrhs = X.shape
    assert n == pack.n
    X = X.astype(jnp.float32)
    nc, ks = pack.num_chunks, pack.ks
    xg = X[pack.src.astype(jnp.int32)].reshape(nc, ks, 128, nrhs)
    stream_spec = pl.BlockSpec((None, ks, 128), lambda j: (j, 0, 0))
    partial = pl.pallas_call(
        functools.partial(_kernel, r_pad=pack.r_pad),
        grid=(nc,),
        in_specs=[stream_spec, stream_spec,
                  pl.BlockSpec((None, ks, nrhs, 128), lambda j: (j, 0, 0, 0))],
        out_specs=pl.BlockSpec((None, nrhs, pack.r_pad), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, nrhs, pack.r_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(interpret),
    )(pack.vals, pack.lrow, jnp.swapaxes(xg, 2, 3))
    y_pad = jnp.zeros((n + pack.r_pad, nrhs), jnp.float32).at[
        pack.fixup_idx].add(jnp.swapaxes(partial, 1, 2).reshape(-1, nrhs))
    return y_pad[:n] + pack.ad.astype(jnp.float32)[:, None] * X


def nnzsplit_spmv(pack: NnzSplitPack, x: jnp.ndarray,
                  interpret=None) -> jnp.ndarray:
    return nnzsplit_spmm(pack, x[:, None], interpret)[:, 0]


# ---------------------------------------------------------------------------
# Shard-local layouts for the distributed strategies
# (consumed through core/schedule.py's memoized builders and the
# ShardSupport entry registered in core/paths.py)
# ---------------------------------------------------------------------------

def _stack_chunked(streams, *, ks: int, r_cap: int):
    """Chunk one stream per shard with equalized (num_chunks, r_pad)."""
    probed = [_chunk_arrays(d, s, v, ks=ks) for d, s, v in streams]
    nc = max(c["num_chunks"] for c in probed)
    rp = max(c["r_pad"] for c in probed)
    if rp > r_cap:
        raise ValueError(f"chunk row window {rp} > cap {r_cap}")
    parts = [_chunk_arrays(d, s, v, ks=ks, num_chunks=nc, r_pad=rp)
             for d, s, v in streams]
    stacked = {key: np.stack([c[key] for c in parts])
               for key in ("vals", "lrow", "src", "chunk_row0", "fixup_idx")}
    return nc, rp, stacked


def _as_shard_arrays(stacked, *, dtype, index_dtype):
    return dict(
        vals=jnp.asarray(stacked["vals"], dtype=dtype),
        lrow=jnp.asarray(stacked["lrow"], dtype=index_dtype),
        src=jnp.asarray(stacked["src"], dtype=index_dtype),
        chunk_row0=jnp.asarray(stacked["chunk_row0"]),
        fixup_idx=jnp.asarray(stacked["fixup_idx"]))


@dataclasses.dataclass(frozen=True)
class NnzSplitShards:
    """Per-shard nnz-split sub-packs in *global* coordinates (allreduce /
    reduce_scatter): shard t chunks only the combined entries whose dest
    row it owns, plus its slice of the diagonal, and emits a full-length
    partial y."""
    p: int
    n: int
    num_chunks: int             # uniform chunks per shard (padded)
    ks: int
    r_pad: int
    vals: jnp.ndarray           # (p, C, KS, 128)
    lrow: jnp.ndarray           # (p, C, KS, 128)
    src: jnp.ndarray            # (p, C*S)
    chunk_row0: jnp.ndarray     # (p, C)
    fixup_idx: jnp.ndarray      # (p, C*r_pad)
    ad: jnp.ndarray             # (p, n) — shard-owned diagonal, zero rest
    num_symmetric: bool

    def shard_pack(self, t: int) -> NnzSplitPack:
        return NnzSplitPack(
            n=self.n, num_chunks=self.num_chunks, ks=self.ks,
            r_pad=self.r_pad, vals=self.vals[t], lrow=self.lrow[t],
            src=self.src[t], chunk_row0=self.chunk_row0[t],
            fixup_idx=self.fixup_idx[t], ad=self.ad[t],
            num_symmetric=self.num_symmetric, pad_ratio=1.0)


def pack_nnzsplit_shards(M: CSRC, starts, ks: int = 8, r_cap: int = 4096,
                         dtype=jnp.float32,
                         index_dtype=jnp.int32) -> NnzSplitShards:
    """Split the combined stream along the row partition ``starts``: shard
    t takes the entries with dest in [starts[t], starts[t+1])."""
    assert M.is_square
    n = M.n
    if index_dtype == jnp.int16 and n > 32767:
        raise ValueError(f"n {n} overflows int16 gather indices")
    starts = np.asarray(starts, dtype=np.int64)
    p = starts.shape[0] - 1
    dest, src, val = _combined_stream(M)

    def streams():
        for t in range(p):
            sel = (dest >= starts[t]) & (dest < starts[t + 1])
            yield dest[sel], src[sel], val[sel]

    nc, rp, stacked = _stack_chunked(list(streams()), ks=ks, r_cap=r_cap)
    ad = np.zeros((p, n), np.float32)
    ad_full = np.asarray(M.ad)
    for t in range(p):
        r0, r1 = int(starts[t]), int(starts[t + 1])
        ad[t, r0:r1] = ad_full[r0:r1]
    return NnzSplitShards(
        p=p, n=n, num_chunks=nc, ks=ks, r_pad=rp,
        ad=jnp.asarray(ad, dtype=dtype),
        num_symmetric=bool(M.numerically_symmetric),
        **_as_shard_arrays(stacked, dtype=dtype, index_dtype=index_dtype))


@dataclasses.dataclass(frozen=True)
class NnzSplitHalo:
    """Per-shard nnz-split packs in *local* halo coordinates: both halves
    of a slot go to the shard owning the slot's row (columns then lie in
    [r0-h, r1), the frame the halo exchange provides), and the local
    product is an n_local = ns + h row vector with the halo rows first —
    the same y_ext/x_ext contract as the other halo layouts."""
    p: int
    ns: int
    h: int
    n_local: int
    num_chunks: int
    ks: int
    r_pad: int
    vals: jnp.ndarray
    lrow: jnp.ndarray
    src: jnp.ndarray
    chunk_row0: jnp.ndarray
    fixup_idx: jnp.ndarray
    ad: jnp.ndarray             # (p, n_local) local-coordinate diagonal
    num_symmetric: bool

    def shard_pack(self, t: int) -> NnzSplitPack:
        return NnzSplitPack(
            n=self.n_local, num_chunks=self.num_chunks, ks=self.ks,
            r_pad=self.r_pad, vals=self.vals[t], lrow=self.lrow[t],
            src=self.src[t], chunk_row0=self.chunk_row0[t],
            fixup_idx=self.fixup_idx[t], ad=self.ad[t],
            num_symmetric=self.num_symmetric, pad_ratio=1.0)


def pack_nnzsplit_halo(M: CSRC, p: int, ks: int = 8, r_cap: int = 4096,
                       dtype=jnp.float32,
                       index_dtype=jnp.int32) -> NnzSplitHalo:
    """Per-shard local packs for the halo strategy.  Same band-fits-shard
    gate as the other halo builders — unstructured matrices with band ~ n
    correctly fail it and fall back to allreduce/reduce_scatter."""
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
    if index_dtype == jnp.int16 and n_local > 32767:
        raise ValueError(f"n_local {n_local} overflows int16 indices")
    ros = row_of_slot(M).astype(np.int64)
    ja = np.asarray(M.ja, dtype=np.int64)
    al = np.asarray(M.al, dtype=np.float32)
    au = np.asarray(M.au, dtype=np.float32)
    shard_of_slot = ros // ns

    def streams():
        for t in range(p):
            sel = shard_of_slot == t
            off = t * ns - h              # global row g -> local g - off
            d = np.concatenate([ros[sel], ja[sel]]) - off
            s = np.concatenate([ja[sel], ros[sel]]) - off
            v = np.concatenate([al[sel], au[sel]])
            order = np.argsort(d, kind="stable")
            yield d[order], s[order], v[order]

    nc, rp, stacked = _stack_chunked(list(streams()), ks=ks, r_cap=r_cap)
    ad = np.zeros((p, n_local), np.float32)
    ad_full = np.asarray(M.ad)
    for t in range(p):
        r0 = t * ns
        r1 = min(n, r0 + ns)
        if r1 > r0:
            ad[t, h:h + (r1 - r0)] = ad_full[r0:r1]
    return NnzSplitHalo(
        p=p, ns=ns, h=h, n_local=n_local, num_chunks=nc, ks=ks, r_pad=rp,
        ad=jnp.asarray(ad, dtype=dtype),
        num_symmetric=bool(M.numerically_symmetric),
        **_as_shard_arrays(stacked, dtype=dtype, index_dtype=index_dtype))


# --- same-structure value refresh of the stacked layouts -------------------

def _refresh_stacked(lay, value_streams, ad_rows):
    """Refill ``vals`` (and ad) of a stacked layout from per-shard value
    streams re-derived in the layout's build order."""
    s = lay.ks * 128
    vals = np.zeros((lay.p, lay.num_chunks, lay.ks, 128), np.float32)
    for t, v in enumerate(value_streams):
        flat = vals[t].reshape(-1)
        flat[:v.shape[0]] = v
    return dataclasses.replace(
        lay,
        vals=jnp.asarray(vals, dtype=lay.vals.dtype),
        ad=jnp.asarray(ad_rows, dtype=lay.ad.dtype))


def refresh_nnzsplit_shards(lay: NnzSplitShards, M: CSRC,
                            starts) -> NnzSplitShards:
    assert M.is_square and M.n == lay.n, "structure mismatch"
    starts = np.asarray(starts, dtype=np.int64)
    dest, _src, val = _combined_stream(M)
    streams = []
    for t in range(lay.p):
        sel = (dest >= starts[t]) & (dest < starts[t + 1])
        streams.append(val[sel])
    ad = np.zeros((lay.p, lay.n), np.float32)
    ad_full = np.asarray(M.ad)
    for t in range(lay.p):
        r0, r1 = int(starts[t]), int(starts[t + 1])
        ad[t, r0:r1] = ad_full[r0:r1]
    return _refresh_stacked(lay, streams, ad)


def refresh_nnzsplit_halo(lay: NnzSplitHalo, M: CSRC) -> NnzSplitHalo:
    assert M.is_square, "structure mismatch"
    ros = row_of_slot(M).astype(np.int64)
    ja = np.asarray(M.ja, dtype=np.int64)
    al = np.asarray(M.al, dtype=np.float32)
    au = np.asarray(M.au, dtype=np.float32)
    shard_of_slot = ros // lay.ns
    streams = []
    for t in range(lay.p):
        sel = shard_of_slot == t
        d = np.concatenate([ros[sel], ja[sel]]) - (t * lay.ns - lay.h)
        v = np.concatenate([al[sel], au[sel]])
        streams.append(v[np.argsort(d, kind="stable")])
    n = M.n
    ad = np.zeros((lay.p, lay.n_local), np.float32)
    ad_full = np.asarray(M.ad)
    for t in range(lay.p):
        r0 = t * lay.ns
        r1 = min(n, r0 + lay.ns)
        if r1 > r0:
            ad[t, lay.h:lay.h + (r1 - r0)] = ad_full[r0:r1]
    return _refresh_stacked(lay, streams, ad)


# --- shard_map plumbing (ShardSupport hooks) -------------------------------

def nnzsplit_shard_arrays(lay):
    """Leading-axis-p arrays a shard_map local function consumes."""
    return (lay.vals, lay.lrow, lay.src, lay.chunk_row0, lay.fixup_idx,
            lay.ad)


def nnzsplit_local_fn(lay, n_local: int, interpret=None, variant="onehot"):
    """Shard-local product: rebuild the shard's pack from the shard_map
    slices (leading axis 1) and dispatch SpMV/SpMM on x's rank."""
    def fn(vals, lrow, src, chunk_row0, fixup_idx, ad, x):
        pk = NnzSplitPack(
            n=n_local, num_chunks=lay.num_chunks, ks=lay.ks,
            r_pad=lay.r_pad, vals=vals[0], lrow=lrow[0], src=src[0],
            chunk_row0=chunk_row0[0], fixup_idx=fixup_idx[0], ad=ad[0],
            num_symmetric=lay.num_symmetric, pad_ratio=1.0)
        if variant == "stream":
            from repro.kernels import csrc_spmv_stream as stream_mod
            return (stream_mod.nnzsplit_spmm_stream(pk, x) if x.ndim == 2
                    else stream_mod.nnzsplit_spmv_stream(pk, x))
        if x.ndim == 2:
            return nnzsplit_spmm(pk, x, interpret=interpret)
        return nnzsplit_spmv(pk, x, interpret=interpret)
    return fn


def nnzsplit_halo_dims(lay: NnzSplitHalo):
    return lay.ns, lay.h, lay.n_local
