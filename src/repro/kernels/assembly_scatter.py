"""Pallas colored-batch + sorted-slot kernels for FEM assembly scatter.

The assembly hot path (repro.assembly.scatter) historically executed one
XLA ``.at[].add`` scatter per color class — C serialized dispatches per
value refresh, the exact launch-bound regime the colored SpMV path left
behind in PR 7.  This module is the streaming formulation of the
scatter-add ``vals[targets[g]] += ke.flat[g]``, consuming the
per-color slot packs the AssemblySchedule precomputes:

  colored-batch   the per-color (C, Lmax) slot/target packs, in two
                  variants dispatched like the SpMV variants:
                    stream   one fused XLA gather of the contribution
                             values + one ``segment_sum`` over the target
                             stream — O(1) work/slot, bandwidth-bound.
                             XLA on every backend: Mosaic lowers neither a
                             1-D gather nor a scatter-add;
                    onehot   the gather in XLA, then a Pallas grid over
                             (output tile, contribution chunk) realizing
                             the targets as a (TILE, CHUNK) one-hot mask
                             contracted on the MXU — compute-bound by
                             construction.
  sorted-slot     the arXiv:2012.00585 analogue: contributions are
                  pre-sorted by destination at schedule-build time, so
                  the whole assembly is ONE color-free gather +
                  ``segment_sum(..., indices_are_sorted=True)`` — a
                  single fused launch, no palette term at all.

Sentinel discipline (shared with csrc_spmv_stream): padded pack entries
carry slot sentinel G (one past the last contribution — the gather reads
an appended zero) and target sentinel ``size`` (one past the last real
segment — the segment-sum drops it).  Index streams arrive int16 when
the schedule's overflow gate allowed it and are upcast before use.  For
dyadic element values every executor is bit-identical to the serial
``np.add.at`` oracle (tests assert equality, not closeness).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csrc_spmv import HIGHEST, LANE_CONTRACT
from repro.runtime import interpret_mode

# output-tile width and contribution-chunk length of the one-hot body:
# each (tile, chunk) program contracts a (TILE, CHUNK) mask on the MXU
ONEHOT_TILE = 512
ONEHOT_CHUNK = 1024
COLORED_VARIANTS = ("stream", "onehot")


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _padded_contribs(kflat) -> jnp.ndarray:
    """Flat contribution values with one appended zero — the slot
    sentinel G gathers it, so padded pack entries are numerically inert."""
    flat = jnp.asarray(kflat, jnp.float32).reshape(-1)
    return jnp.concatenate([flat, jnp.zeros((1,), jnp.float32)])


# ---------------------------------------------------------------------------
# Fused XLA executors (the stream variant and the sorted-slot strategy)
# ---------------------------------------------------------------------------

def colored_scatter_fused(color_slots, color_targets, kflat,
                          size: int) -> jnp.ndarray:
    """All color batches as one gather + one segment-sum over the packs'
    (slot, target) pairs.  Target sentinel ``size`` routes padding to the
    drop segment one past the vector end."""
    kpad = _padded_contribs(kflat)
    slots = jnp.asarray(color_slots).astype(jnp.int32).reshape(-1)
    tgts = jnp.asarray(color_targets).astype(jnp.int32).reshape(-1)
    contribs = jnp.take(kpad, slots)
    out = jax.ops.segment_sum(contribs, tgts, num_segments=size + 1)
    return out[:size]


def sorted_scatter(sorted_perm, sorted_targets, kflat,
                   size: int) -> jnp.ndarray:
    """Sorted-slot assembly: gather contributions in destination order,
    then one monotone segment-sum — no colors, no sentinels, one launch."""
    kvals = jnp.asarray(kflat, jnp.float32).reshape(-1)
    contribs = jnp.take(kvals, jnp.asarray(sorted_perm).astype(jnp.int32))
    return jax.ops.segment_sum(
        contribs, jnp.asarray(sorted_targets).astype(jnp.int32),
        num_segments=size, indices_are_sorted=True)


# ---------------------------------------------------------------------------
# One-hot Pallas kernel (grid = output tiles x contribution chunks)
# ---------------------------------------------------------------------------

def _onehot_kernel(contrib_ref, tgt_ref, out_ref, *, tile: int):
    """One (output tile, contribution chunk) program: the (TILE, LC)
    one-hot of the chunk's tile-local targets contracted with its
    contributions.  Out-of-tile targets (including the sentinel) match no
    iota row and add nothing; the tile's block is revisited across the
    inner chunk axis."""
    t = pl.program_id(0)
    local = tgt_ref[...] - t * tile                         # (1, LC)
    iota = jax.lax.broadcasted_iota(jnp.int32, (tile, local.shape[1]), 0)
    onehot = (iota == local).astype(jnp.float32)            # (TILE, LC)
    win = jax.lax.dot_general(contrib_ref[...], onehot, LANE_CONTRACT,
                              precision=HIGHEST,
                              preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = win

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        out_ref[...] += win


def colored_scatter_onehot(color_slots, color_targets, kflat, size: int,
                           interpret=None) -> jnp.ndarray:
    """The one-hot variant: the (C, Lmax) packs' contributions are gathered
    in XLA (Mosaic has no 1-D gather), cut into ONEHOT_CHUNK-lane chunks,
    and scattered by the Pallas kernel as MXU contractions per output
    tile.  O(size · C·Lmax) work: compute-bound by construction, the
    cost model prices it so."""
    kpad = _padded_contribs(kflat)
    slots = jnp.asarray(color_slots).astype(jnp.int32).reshape(-1)
    tgts = jnp.asarray(color_targets).astype(jnp.int32).reshape(-1)
    contribs = jnp.take(kpad, slots)
    chunk = min(ONEHOT_CHUNK, _round_up(contribs.shape[0], 128))
    g_pad = _round_up(contribs.shape[0], chunk)
    contribs = jnp.pad(contribs, (0, g_pad - contribs.shape[0]))
    tgts = jnp.pad(tgts, (0, g_pad - tgts.shape[0]), constant_values=size)
    nch = g_pad // chunk
    size_pad = _round_up(size + 1, ONEHOT_TILE)
    nt = size_pad // ONEHOT_TILE
    chunk_spec = pl.BlockSpec((None, 1, chunk), lambda t, c: (c, 0, 0))
    out = pl.pallas_call(
        functools.partial(_onehot_kernel, tile=ONEHOT_TILE),
        grid=(nt, nch),
        in_specs=[chunk_spec, chunk_spec],
        out_specs=pl.BlockSpec((None, 1, ONEHOT_TILE),
                               lambda t, c: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nt, 1, ONEHOT_TILE), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(contribs.reshape(nch, 1, chunk), tgts.reshape(nch, 1, chunk))
    return out.reshape(-1)[:size]


def colored_scatter(color_slots, color_targets, kflat, size: int,
                    variant: str = "stream",
                    interpret=None) -> jnp.ndarray:
    """Variant dispatch: 'stream' is the fused XLA form on every backend,
    'onehot' the Pallas kernel."""
    if variant not in COLORED_VARIANTS:
        raise ValueError(
            f"variant {variant!r} not in {COLORED_VARIANTS}")
    if variant == "stream":
        return colored_scatter_fused(color_slots, color_targets, kflat,
                                     size)
    return colored_scatter_onehot(color_slots, color_targets, kflat, size,
                                  interpret=interpret)
