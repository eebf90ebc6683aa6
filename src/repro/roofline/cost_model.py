"""Analytic per-candidate roofline costs for ExecutionPlans.

The tuner's predict-then-measure mode (core/tuner.py) needs a ranking of
candidate plans *before* any of them is packed or timed.  This module
prices a candidate from matrix statistics alone — the same geometry
formulas the packers use (window width, tile count, slot padding), but
evaluated on MatrixStats instead of a built pack:

  bytes   the streamed working set per product: value streams (halved for
          numerically-symmetric matrices and again for bfloat16), index
          streams (halved for int16), x/y traffic, and the per-tile window
          writes + overlap-add re-reads;
  flops   O(1)-per-slot multiply-adds for the streaming/segment variants;
          the one-hot variants additionally pay the (S, W) mask build
          (iota + compare + convert, one op per mask element) and the
          dot_general contractions, 2·S·W·nrhs flops each — which is
          exactly why one-hot is compute-bound and stream is not;
  predicted_s = max(bytes / HBM_BW, flops / PEAK_FLOPS_BF16), priced on
          the target chip's peaks (the v5e row of ``DEVICE_PEAKS``).

Predictions are used for *ranking* (measure only the top-K), never as a
substitute for measurement.  ``roofline_fraction`` — the least time the
measuring device could take for the candidate's bytes and flops over the
measured time — is recorded only for a device kind listed in
``DEVICE_PEAKS``; on any other device (the CPU host) it is ``None``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.plan import ExecutionPlan, kernel_window, LANES

# Published peaks per chip, keyed by ``jax.Device.device_kind``.  A
# roofline share is computed only against a row of this table.
DEVICE_PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
PEAK_FLOPS_BF16 = DEVICE_PEAKS["TPU v5 lite"]["bf16_flops_per_s"]


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


_VALUE_BYTES = {"float32": 4, "bfloat16": 2}
_INDEX_BYTES = {"int32": 4, "int16": 2}

# --- colorful-path locality terms -----------------------------------------
# Per-color serial launch overhead: each color class is its own scatter
# dispatch, serialized against the previous one, so the colored path pays
# this once per palette entry — the term that makes a 49-color greedy
# schedule price above a 4-color RACE schedule on the same bytes.
COLOR_LAUNCH_S = 2e-6
# Scatter transaction granularity: an isolated y/x touch moves a whole
# line, using only the 4 bytes it wanted.  Classes whose rows stride the
# matrix (greedy destroys row locality — the paper's §3.2 criticism) pay
# the waste on most touches; RACE classes are unions of contiguous level
# ranges, so neighbouring rows share lines and most of the waste vanishes.
SCATTER_LINE_BYTES = 64.0
_REUSE_WASTE_FRACTION = {"greedy": 1.0, "race": 0.25}


def _coloring_palette_estimate(stats, provider: str) -> float:
    """Analytic palette-size estimate for the distance-2 row coloring.

    greedy first-fit needs about the conflict degree + 1 colors: on banded
    matrices the distance-2 conflict degree is ~2·bandwidth, on
    unstructured ones ~deg² (capped at n-1).  RACE's bipartition needs two
    sweeps per recursion depth, and the depth the chunk-size target forces
    is shallow (one or two) on every class we generate — so it is modeled
    as a small constant palette, which is exactly its empirical behaviour
    (2–10 colors where greedy needs 30–70).
    """
    n = max(stats.n, 1)
    deg = 2.0 * stats.k / n
    conflict_deg = min(float(n - 1), 2.0 * stats.bandwidth,
                       deg * deg + deg)
    if provider == "race":
        return 4.0                       # two sweeps x ~one recursion level
    return 1.0 + conflict_deg


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    """Roofline price of one candidate plan on one matrix class."""
    bytes: float                  # streamed bytes per product
    flops: float                  # arithmetic ops per product
    memory_s: float               # bytes / HBM_BW
    compute_s: float              # flops / PEAK_FLOPS_BF16
    predicted_s: float            # max(memory_s, compute_s)

    @property
    def bound(self) -> str:
        return "compute" if self.compute_s > self.memory_s else "memory"

    def to_dict(self) -> Dict:
        return {"bytes": self.bytes, "flops": self.flops,
                "predicted_ms": self.predicted_s * 1e3, "bound": self.bound}


def _windowed_geometry(stats, plan: ExecutionPlan) -> Tuple[int, int, int]:
    """(nt, w_pad, padded slot count) of a 'kernel'/'flat' pack, estimated
    from stats — mirrors blockell.pack / pack_flat without building them."""
    tm = plan.tm
    nt = max(1, -(-stats.n // tm))
    w_pad = kernel_window(tm, stats.bandwidth)
    k_step = plan.k_step_sublanes * LANES
    if plan.path == "flat":
        # per-tile-exact packing: ceil(k / k_step) full steps plus at most
        # one remainder step per tile (rows never share a step across
        # tiles), so padding stays O(nt·k_step) regardless of skew
        steps = -(-max(stats.k, 1) // k_step) + nt
        return nt, w_pad, steps * k_step
    # rectangular grid: every tile is padded to the fullest tile's slot
    # count, so skew inflates the pack — model it with the nnz-per-row
    # dispersion (a tile of tm rows concentrates ~tm·dev of excess)
    mean_tile = max(stats.k, 1) / nt
    imbalance = 1.0 + stats.nnz_row_dev / max(stats.nnz_row_mean, 1.0)
    s_tile = _round_up(max(int(mean_tile * imbalance), 1), k_step)
    return nt, w_pad, nt * s_tile


def _nnzsplit_geometry(stats, plan: ExecutionPlan) -> Tuple[int, int, int]:
    """(num_chunks, r_pad, padded entry count): the dest-sorted stream has
    one entry per triangle half (2k total), cut into S = ks·128 chunks."""
    s = plan.k_step_sublanes * LANES
    entries = max(2 * stats.k, 1)
    num_chunks = -(-entries // s)
    # a chunk of S entries spans ~S / (nnz per row) rows
    span = s / max(stats.nnz_row_mean, 1.0)
    r_pad = _round_up(max(int(span), 1), 128)
    return num_chunks, r_pad, num_chunks * s


def plan_cost(stats, plan: ExecutionPlan) -> CostEstimate:
    """Roofline price of one candidate.  Any registered path prices at
    least as the generic streaming product (the segment formula), so a
    future path joins predict-then-measure without editing this module."""
    nrhs = max(plan.nrhs, 1)
    vb = _VALUE_BYTES.get(plan.value_dtype, 4)
    ib = _INDEX_BYTES.get(plan.index_dtype, 4)
    n, k = stats.n, max(stats.k, 1)
    vstreams = 1 if stats.numerically_symmetric else 2
    xy = 2.0 * 4 * max(n, stats.m) * nrhs      # x read + y write
    diag = 4.0 * n
    launch_s = 0.0                             # serialized dispatch overhead

    if plan.path in ("kernel", "flat"):
        nt, w_pad, slots = _windowed_geometry(stats, plan)
        byts = (slots * (vb * vstreams + ib * 2)   # vals + col/row streams
                + diag + xy
                + 2.0 * nt * w_pad * 4 * nrhs)     # windows + overlap-add
        flops = 4.0 * slots * nrhs + 2.0 * n * nrhs
        if plan.variant == "onehot":
            # two (S, W) masks: iota + compare + convert per element, then
            # four dot_generals at 2·S·W·nrhs each
            flops += slots * w_pad * (6.0 + 8.0 * nrhs)
    elif plan.path == "nnzsplit":
        nc, r_pad, slots = _nnzsplit_geometry(stats, plan)
        byts = (slots * (vb + 4 + ib)     # vals + src gather idx + lrow
                + diag + xy
                + 2.0 * nc * r_pad * 4 * nrhs)     # partials + fixup
        flops = 2.0 * slots * nrhs + 2.0 * n * nrhs
        if plan.variant == "onehot":
            flops += slots * r_pad * (3.0 + 2.0 * nrhs)
    elif plan.path == "colorful":
        # colored execution streams the triangle once in total (the color
        # classes tile the slots), but adds the two locality terms: one
        # serialized scatter launch per color, and the reuse-distance
        # penalty — scattered classes touch x/y one isolated line per
        # element (2k + n targets per product), contiguous RACE level
        # groups touch dense lines
        colors = _coloring_palette_estimate(stats, plan.coloring)
        waste = _REUSE_WASTE_FRACTION.get(plan.coloring, 1.0)
        byts = k * (4 * vstreams + 4 * 2) + diag + xy
        byts += waste * (2.0 * k + n) * (SCATTER_LINE_BYTES - 4.0)
        flops = 4.0 * k * nrhs + 2.0 * n * nrhs
        launch_s = colors * COLOR_LAUNCH_S
    else:
        # segment / future paths: the unpadded streaming product
        byts = k * (4 * vstreams + 4 * 2) + diag + xy
        flops = 4.0 * k * nrhs + 2.0 * n * nrhs

    mem_s = byts / HBM_BW
    cmp_s = flops / PEAK_FLOPS_BF16
    return CostEstimate(bytes=float(byts), flops=float(flops),
                        memory_s=mem_s, compute_s=cmp_s,
                        predicted_s=max(mem_s, cmp_s) + launch_s)


def rank_plans(stats, plans: Sequence[ExecutionPlan]
               ) -> List[Tuple[ExecutionPlan, CostEstimate]]:
    """Candidates cheapest-first by predicted per-RHS-column time (the
    tuner's argmin metric — an nrhs=8 plan prices 8 columns of work)."""
    priced = [(p, plan_cost(stats, p)) for p in plans]
    priced.sort(key=lambda pc: pc[1].predicted_s / max(pc[0].nrhs, 1))
    return priced


def roofline_fraction(est: CostEstimate, measured_s: float,
                      device_kind: str) -> Optional[float]:
    """Least time ``device_kind`` could take for the candidate's bytes and
    flops, over the measured time; ``None`` for a device kind without a
    peak row (no number is made up from another chip's peaks)."""
    peaks = DEVICE_PEAKS.get(device_kind)
    if peaks is None or measured_s <= 0:
        return None
    least = max(est.bytes / peaks["hbm_bytes_per_s"],
                est.flops / peaks["bf16_flops_per_s"])
    return least / measured_s


# ---------------------------------------------------------------------------
# FEM assembly scatter pricing (repro.assembly.scatter.tune_assembly)
# ---------------------------------------------------------------------------

def assembly_cost(sched, strategy: str,
                  variant: str = "stream") -> CostEstimate:
    """Roofline price of one assembly value refresh for a (strategy,
    variant) candidate on an AssemblySchedule (duck-typed: ne, edof,
    size, num_buffers, coloring, and the kernel packs).

    All strategies stream the G = ne·edof² contribution values plus
    their index streams (halved under the int16 gate) and write the
    size-length unified vector.  What separates them are the overhead
    terms: the colored-batch kernels pay the (C, Lmax) pack padding;
    the one-hot body additionally builds an (L, TILE) mask per output
    tile (iota + compare + convert + 2-op contraction per element —
    compute-bound by construction); the legacy per-color baseline pays
    one serialized scatter launch per palette entry plus the isolated
    scatter-line waste; private pays 2·B·size partial traffic for the
    buffer reduce; sorted-slot streams exactly G with none of the above
    — which is precisely when it beats colored (docs/DESIGN.md §10)."""
    from repro.kernels.assembly_scatter import ONEHOT_TILE

    contribs = float(sched.ne * sched.edof * sched.edof)   # G
    size = float(sched.size)
    out_bytes = size * 4.0
    ib_slot = _INDEX_BYTES.get(str(sched.color_slots.dtype), 4)
    ib_tgt = _INDEX_BYTES.get(str(sched.color_targets.dtype), 4)
    launch_s = 0.0

    if strategy == "colored" and variant == "percolor":
        colors = int(sched.coloring.num_colors)
        byts = contribs * (4.0 + 4.0) + out_bytes
        # each color's targets stride the unified vector: isolated
        # line-granularity touches, like the colorful SpMV path
        byts += contribs * (SCATTER_LINE_BYTES - 4.0)
        flops = contribs
        launch_s = colors * COLOR_LAUNCH_S
    elif strategy == "colored":
        padded = float(sched.color_slots.shape[0]
                       * sched.color_slots.shape[1])       # C·Lmax
        byts = padded * (4.0 + ib_slot + ib_tgt) + out_bytes
        flops = padded
        if variant == "onehot":
            # per (color, tile) program: an (L, TILE) mask — iota +
            # compare + convert (3 ops) + the 2-op dot contraction —
            # over ceil((size+1)/TILE) tiles
            size_pad = float(_round_up(int(size) + 1, ONEHOT_TILE))
            flops += padded * size_pad * 5.0
    elif strategy == "sorted":
        byts = contribs * (4.0 + ib_slot + ib_tgt) + out_bytes
        flops = contribs
    elif strategy == "private":
        buffers = float(sched.num_buffers)
        # partials written then re-read for the reduce
        byts = (contribs * (4.0 + 4.0) + out_bytes
                + 2.0 * buffers * (size + 1.0) * 4.0)
        flops = contribs + buffers * size
    else:                              # serial oracle — not a candidate
        byts = contribs * (4.0 + 4.0) + out_bytes
        byts += contribs * (SCATTER_LINE_BYTES - 4.0)
        flops = contribs

    mem_s = byts / HBM_BW
    cmp_s = flops / PEAK_FLOPS_BF16
    return CostEstimate(bytes=float(byts), flops=float(flops),
                        memory_s=mem_s, compute_s=cmp_s,
                        predicted_s=max(mem_s, cmp_s) + launch_s)


def rank_assembly_candidates(
        sched, candidates: Sequence[Tuple[str, str]]
        ) -> List[Tuple[Tuple[str, str], CostEstimate]]:
    """(strategy, variant) candidates cheapest-first by predicted time —
    the assembly tuner's measure-ordering (tune_assembly)."""
    priced = [(sv, assembly_cost(sched, sv[0], sv[1]))
              for sv in candidates]
    priced.sort(key=lambda pc: pc[1].predicted_s)
    return priced
