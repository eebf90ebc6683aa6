"""Conflict-free global-matrix assembly: the paper's accumulation
strategies applied to the FEM scatter-add (docs/DESIGN.md §5).

Assembling ``A = Σ_e P_e^T k_e P_e`` is a scatter-add over CSRC slots:
contribution (e, a, b) lands on the diagonal (i == j), on a lower slot
``al[p]`` (i > j) or on the aligned upper slot ``au[p]`` (i < j).  All
three destinations flatten into one **unified value vector**
``[ad | al | au]`` of length n + 2k, so assembly is a single scatter into
that vector and the two race-avoidance families of the paper map exactly:

  colored   per-color batched scatter (elements of one color share no
            DOF ⇒ within a color every target is written once ⇒ a
            permutation write, like the colorful SpMV path §3.2).
            Executed by the fused colored-batch kernels of
            ``repro.kernels.assembly_scatter`` (stream/onehot variants,
            one launch total); the legacy one-XLA-scatter-per-color
            discipline survives as ``variant='percolor'`` — the
            baseline the kernels are benchmarked against
  sorted    contributions pre-sorted by destination slot at
            schedule-build time, so assembly is ONE color-free
            monotone segment-sum (the atomics-style GPU assembly
            format of arXiv:2012.00585, docs/DESIGN.md §10)
  private   per-buffer full-length partials reduced at the end (the
            local-buffers / all-in-one accumulation family §3.1)
  serial    numpy ``np.add.at`` in element order — the ground-truth
            oracle the strategies must reproduce

With the dyadic-quantized stiffness synthesis of ``assembly/mesh.py``
float32 accumulation is exact in any order, so the strategies are
required to agree with the oracle **bit-for-bit** (tests and the CI
assembly smoke assert equality, not closeness).

All structure-dependent precompute — slot layout, contribution targets,
element coloring, buffer grouping — lives in the npz-serializable
:class:`AssemblySchedule`, stored in the tuner's PlanCache next to the
SpMV schedules and keyed by a **connectivity digest**: FEM time stepping
re-assembles with unchanged connectivity and must reuse every artifact
(the ``BUILD_COUNTS['assembly_schedule']`` probe asserts zero rebuilds).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Dict, Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import csrc
from repro.core.coloring import Coloring
# the shared build probe (re-exported as schedule.BUILD_COUNTS): assembly
# builds count into the same Counter the SpMV schedule layer uses
from repro.core.paths import BUILD_COUNTS
from repro import obs
from repro.kernels import assembly_scatter as akern
from repro.kernels.assembly_scatter import COLORED_VARIANTS  # noqa: F401
from repro.runtime import jit_hoisted, to_host
from .conflict import color_elements, element_dofs
from .mesh import Mesh

# version 3: the schedule carries the kernel slot packs — per-color
# (slots, targets) streams and the destination-sorted permutation — with
# overflow-gated int16 index dtypes.  Version-2 files load as misses and
# are rebuilt transparently (version 2 added the coloring provider).
ASSEMBLY_VERSION = 3

STRATEGIES = ("colored", "sorted", "private", "serial")

# the (strategy, variant) pool tune_assembly prices and measures; variant
# labels the executor ('percolor' = the legacy one-scatter-per-color
# XLA baseline, 'vmap'/'numpy' are the single executors of their strategy)
ASSEMBLY_CANDIDATES = (
    ("colored", "stream"), ("colored", "onehot"), ("colored", "percolor"),
    ("sorted", "stream"), ("private", "vmap"))

_DEFAULT_VARIANT = {"colored": "stream", "sorted": "stream",
                    "private": "vmap", "serial": "numpy"}

# int16 index streams iff every representable value (including the
# sentinel one past the real range) fits — same overflow gate as the
# SpMV window streams (core/blockell.pack)
_INT16_MAX = np.iinfo(np.int16).max


def assembly_key(digest: str, num_buffers: int,
                 coloring: str = "greedy") -> str:
    """Cache key of one assembly schedule.  Greedy keys are byte-identical
    to pre-provider caches; other providers append their name."""
    suffix = "" if coloring == "greedy" else f".{coloring}"
    return f"asm-{digest}.b{num_buffers}{suffix}"


@dataclasses.dataclass(frozen=True)
class AssemblySchedule:
    """Every structure-dependent precomputation one connectivity needs to
    assemble CSRC matrices, for any number of value refreshes."""

    structure_digest: str       # connectivity digest (see structure_digest)
    n: int                      # global DOFs
    k: int                      # strictly-lower CSRC slots
    ne: int                     # elements
    edof: int                   # DOFs per element
    ndof_per_node: int
    num_buffers: int            # private-buffer strategy width
    ia: np.ndarray              # (n+1,) CSRC lower-triangle row pointers
    ja: np.ndarray              # (k,)
    # contribution (e, a, b) at flat index e·edof² + a·edof + b scatters to
    # targets[...] in the unified [ad | al | au] vector of length n + 2k
    targets: np.ndarray         # (ne·edof²,) int32
    coloring: Coloring          # element coloring (conflict.color_elements)
    buffer_elements: np.ndarray  # (num_buffers, epb) int32, -1 = padding
    # --- kernel slot packs (version 3) -------------------------------
    # per-color contribution streams, padded to a rectangular (C, Lmax)
    # table: slots index the flat ke (sentinel = ne·edof², gathers an
    # appended zero), targets index the unified vector (sentinel = size,
    # the segment-sum drop slot).  int16 when the overflow gate allows.
    color_slots: np.ndarray      # (C, Lmax) int16|int32
    color_targets: np.ndarray    # (C, Lmax) int16|int32
    # destination-sorted permutation of all contributions (sorted-slot
    # strategy): perm gathers ke.flat, sorted_targets is monotone
    sorted_perm: np.ndarray      # (ne·edof²,) int16|int32
    sorted_targets: np.ndarray   # (ne·edof²,) int16|int32

    @property
    def size(self) -> int:
        """Length of the unified value vector."""
        return self.n + 2 * self.k

    @property
    def index_dtypes(self) -> Dict[str, str]:
        """Gated dtypes of the kernel index streams (bench provenance)."""
        return {"slots": str(self.color_slots.dtype),
                "targets": str(self.color_targets.dtype)}

    def key(self) -> str:
        return assembly_key(self.structure_digest, self.num_buffers,
                            self.coloring.provider)

    # ------------------------------------------------------------------
    # Serialization (npz arrays + JSON meta, SpmvSchedule conventions)
    # ------------------------------------------------------------------

    def save_npz(self, path: str):
        meta = {
            "version": ASSEMBLY_VERSION,
            "structure_digest": self.structure_digest,
            "n": self.n, "k": self.k, "ne": self.ne, "edof": self.edof,
            "ndof_per_node": self.ndof_per_node,
            "num_buffers": self.num_buffers,
            "num_colors": int(self.coloring.num_colors),
            "coloring_provider": self.coloring.provider,
        }
        arrays = dict(
            ia=np.asarray(self.ia), ja=np.asarray(self.ja),
            targets=np.asarray(self.targets),
            color_of_row=np.asarray(self.coloring.color_of_row),
            rows_by_color=np.asarray(self.coloring.rows_by_color),
            color_ptr=np.asarray(self.coloring.color_ptr),
            buffer_elements=np.asarray(self.buffer_elements),
            color_slots=np.asarray(self.color_slots),
            color_targets=np.asarray(self.color_targets),
            sorted_perm=np.asarray(self.sorted_perm),
            sorted_targets=np.asarray(self.sorted_targets),
        )
        # RACE level-group metadata survives the round-trip so reloaded
        # schedules keep the chunk-aware invariant verifiable
        if self.coloring.level_of_row is not None:
            arrays["color_level_of_row"] = np.asarray(
                self.coloring.level_of_row)
        if self.coloring.group_of_row is not None:
            arrays["color_group_of_row"] = np.asarray(
                self.coloring.group_of_row)
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp.npz"
        with open(tmp, "wb") as f:
            np.savez_compressed(f, __meta__=np.frombuffer(
                json.dumps(meta, sort_keys=True).encode(), dtype=np.uint8),
                **arrays)
        os.replace(tmp, path)

    @classmethod
    def load_npz(cls, path: str) -> "AssemblySchedule":
        with np.load(path) as z:
            meta = json.loads(bytes(z["__meta__"]).decode())
            if meta.get("version") != ASSEMBLY_VERSION:
                raise ValueError(
                    f"assembly schedule {path}: version "
                    f"{meta.get('version')!r} != {ASSEMBLY_VERSION}")
            coloring = Coloring(
                color_of_row=z["color_of_row"],
                num_colors=int(meta["num_colors"]),
                rows_by_color=z["rows_by_color"],
                color_ptr=z["color_ptr"],
                provider=meta.get("coloring_provider", "greedy"),
                level_of_row=(z["color_level_of_row"]
                              if "color_level_of_row" in z.files else None),
                group_of_row=(z["color_group_of_row"]
                              if "color_group_of_row" in z.files else None))
            return cls(structure_digest=meta["structure_digest"],
                       n=meta["n"], k=meta["k"], ne=meta["ne"],
                       edof=meta["edof"],
                       ndof_per_node=meta["ndof_per_node"],
                       num_buffers=meta["num_buffers"],
                       ia=z["ia"], ja=z["ja"], targets=z["targets"],
                       coloring=coloring,
                       buffer_elements=z["buffer_elements"],
                       color_slots=z["color_slots"],
                       color_targets=z["color_targets"],
                       sorted_perm=z["sorted_perm"],
                       sorted_targets=z["sorted_targets"])


def structure_digest(conn: np.ndarray, ndof_per_node: int = 1,
                     num_nodes: Optional[int] = None) -> str:
    """Digest of the element connectivity (the assembly-side analog of
    ``schedule.structure_digest``): unchanged connectivity ⇒ identical
    slot layout, targets, coloring, and buffer grouping."""
    conn = np.ascontiguousarray(np.asarray(conn, np.int64))
    num_nodes = int(conn.max()) + 1 if num_nodes is None else num_nodes
    h = hashlib.sha1()
    h.update(np.asarray([conn.shape[0], conn.shape[1], num_nodes,
                         ndof_per_node], np.int64).tobytes())
    h.update(conn.tobytes())
    return h.hexdigest()[:16]


def _index_dtype(max_value: int):
    """Narrowest stream dtype that holds every value up to ``max_value``
    (the sentinel, one past the real range) — the SpMV int16 overflow
    gate applied to assembly index streams."""
    return np.int16 if max_value <= _INT16_MAX else np.int32


def _pack_colored(targets: np.ndarray, coloring: Coloring, edof2: int,
                  size: int) -> Tuple[np.ndarray, np.ndarray]:
    """The colored-batch kernel's (C, Lmax) slot/target streams.

    Row c lists color c's contribution indices (element-major) and their
    destinations, lane-aligned to a multiple of 128 and padded with the
    sentinels the kernels drop (slot = G reads the appended zero, target
    = size lands in the drop segment)."""
    num_contribs = int(targets.size)
    counts = [len(coloring.rows(c)) * edof2
              for c in range(coloring.num_colors)]
    lmax = max(128, -(-max(counts + [1]) // 128) * 128)
    slot_dt = _index_dtype(num_contribs)
    tgt_dt = _index_dtype(size)
    color_slots = np.full((coloring.num_colors, lmax), num_contribs,
                          dtype=slot_dt)
    color_targets = np.full((coloring.num_colors, lmax), size,
                            dtype=tgt_dt)
    lane = np.arange(edof2, dtype=np.int64)
    for c in range(coloring.num_colors):
        els = np.asarray(coloring.rows(c), np.int64)
        if els.size == 0:
            continue
        sl = (els[:, None] * edof2 + lane).reshape(-1)
        color_slots[c, :sl.size] = sl.astype(slot_dt)
        color_targets[c, :sl.size] = targets[sl].astype(tgt_dt)
    return color_slots, color_targets


def _pack_sorted(targets: np.ndarray,
                 size: int) -> Tuple[np.ndarray, np.ndarray]:
    """The sorted-slot strategy's destination order: a stable argsort of
    the targets (build-time work) so the value refresh is one monotone
    segment-sum with no coloring at all."""
    num_contribs = int(targets.size)
    perm = np.argsort(targets, kind="stable")
    sorted_perm = perm.astype(_index_dtype(num_contribs))
    sorted_targets = targets[perm].astype(_index_dtype(size))
    return sorted_perm, sorted_targets


def build_assembly_schedule(mesh_or_conn: Union[Mesh, np.ndarray],
                            ndof_per_node: int = 1,
                            num_buffers: int = 8,
                            num_nodes: Optional[int] = None,
                            coloring: Optional[Coloring] = None,
                            coloring_provider: str = "greedy"
                            ) -> AssemblySchedule:
    """Build the full assembly artifact for one connectivity.

    The slot layout (ia/ja) is the union of every element's dense block,
    lower triangle only — structurally symmetric by construction, so the
    assembled matrix needs no :func:`~repro.core.csrc.symmetrize_pattern`
    pass.  Contribution targets are resolved once via searchsorted on the
    sorted lower-slot keys; the element coloring and the private-buffer
    grouping ride along.
    """
    if isinstance(mesh_or_conn, Mesh):
        conn = mesh_or_conn.conn
        num_nodes = mesh_or_conn.num_nodes
    else:
        conn = np.asarray(mesh_or_conn)
        num_nodes = (int(conn.max()) + 1 if num_nodes is None
                     else num_nodes)
    BUILD_COUNTS.inc("assembly_schedule")
    d = ndof_per_node
    n = num_nodes * d
    with obs.span("assembly.build_schedule", ndof_per_node=d):
        ed = element_dofs(conn, d)                 # (ne, edof)
        ne, edof = ed.shape

        with obs.span("assembly.slot_pack", ne=ne, edof=edof):
            ii = np.broadcast_to(ed[:, :, None],
                                 (ne, edof, edof)).reshape(-1)
            jj = np.broadcast_to(ed[:, None, :],
                                 (ne, edof, edof)).reshape(-1)
            ii = ii.astype(np.int64)
            jj = jj.astype(np.int64)

            low = ii > jj
            keys = np.unique(ii[low] * n + jj[low])  # sorted lower slots
            k = int(keys.shape[0])
            rows = (keys // n).astype(np.int64)
            ja = (keys % n).astype(np.int32)
            ia = np.zeros(n + 1, dtype=np.int32)
            np.add.at(ia, rows + 1, 1)
            ia = np.cumsum(ia, dtype=np.int32)

            targets = np.empty(ne * edof * edof, dtype=np.int32)
            diag = ii == jj
            targets[diag] = ii[diag]
            targets[low] = n + np.searchsorted(keys, ii[low] * n + jj[low])
            up = ii < jj
            targets[up] = n + k + np.searchsorted(keys,
                                                  jj[up] * n + ii[up])

        if coloring is None:
            BUILD_COUNTS.inc("element_coloring")
            with obs.span("assembly.element_coloring",
                          provider=coloring_provider):
                coloring = color_elements(conn, provider=coloring_provider)

        size = n + 2 * k
        BUILD_COUNTS.inc("assembly_color_pack")
        with obs.span("assembly.color_pack",
                      num_colors=int(coloring.num_colors)):
            color_slots, color_targets = _pack_colored(
                targets, coloring, edof * edof, size)
        BUILD_COUNTS.inc("assembly_sorted_pack")
        with obs.span("assembly.sorted_pack", contributions=targets.size):
            sorted_perm, sorted_targets = _pack_sorted(targets, size)

    # private-buffer grouping: contiguous element chunks (locality), padded
    # to a rectangular (B, epb) table with -1 sentinels
    B = max(1, min(num_buffers, ne))
    epb = -(-ne // B)
    buffer_elements = np.full((B, epb), -1, dtype=np.int32)
    flat = buffer_elements.reshape(-1)
    flat[:ne] = np.arange(ne, dtype=np.int32)

    return AssemblySchedule(
        structure_digest=structure_digest(conn, d, num_nodes),
        n=n, k=k, ne=ne, edof=edof, ndof_per_node=d, num_buffers=B,
        ia=ia, ja=ja, targets=targets, coloring=coloring,
        buffer_elements=buffer_elements,
        color_slots=color_slots, color_targets=color_targets,
        sorted_perm=sorted_perm, sorted_targets=sorted_targets)


def assembly_schedule_for(mesh_or_conn, ndof_per_node: int = 1,
                          num_buffers: int = 8, cache=None,
                          num_nodes: Optional[int] = None,
                          coloring_provider: str = "greedy"
                          ) -> AssemblySchedule:
    """The schedule to assemble this connectivity with — cache hit wins.

    ``cache`` is a :class:`~repro.core.tuner.PlanCache`; a hit (keyed by
    the connectivity digest and the element-coloring provider) performs
    zero structural work, which is the FEM time-stepping fast path:
    re-assembly with unchanged connectivity only refreshes value streams.
    """
    if cache is None:
        return build_assembly_schedule(mesh_or_conn, ndof_per_node,
                                       num_buffers, num_nodes=num_nodes,
                                       coloring_provider=coloring_provider)
    if isinstance(mesh_or_conn, Mesh):
        conn, nn = mesh_or_conn.conn, mesh_or_conn.num_nodes
    else:
        conn = np.asarray(mesh_or_conn)
        nn = int(conn.max()) + 1 if num_nodes is None else num_nodes
    digest = structure_digest(conn, ndof_per_node, nn)
    # same clamp the builder applies, so lookup and stored keys agree on
    # meshes with fewer elements than buffers
    num_buffers = max(1, min(num_buffers, int(conn.shape[0])))
    hit = cache.get_assembly_schedule(digest, num_buffers,
                                      coloring=coloring_provider)
    if hit is not None:
        return hit
    sched = build_assembly_schedule(conn, ndof_per_node, num_buffers,
                                    num_nodes=nn,
                                    coloring_provider=coloring_provider)
    cache.put_assembly_schedule(sched)
    return sched


# ---------------------------------------------------------------------------
# Accumulation strategies
# ---------------------------------------------------------------------------

def scatter_colored_percolor(sched: AssemblySchedule, ke) -> jnp.ndarray:
    """The legacy per-color discipline: one XLA ``.at[].add`` scatter per
    color class, serialized — C dispatches per refresh.  Kept as the
    baseline the fused colored-batch kernels are benchmarked against
    (CI asserts a Pallas strategy beats it on the tet suite)."""
    kflat = jnp.asarray(ke, jnp.float32).reshape(sched.ne, -1)
    t2 = np.asarray(sched.targets).reshape(sched.ne, -1)
    vals = jnp.zeros(sched.size, jnp.float32)
    col = sched.coloring
    for c in range(col.num_colors):
        els = np.asarray(col.rows(c))
        if els.size == 0:
            continue
        tg = jnp.asarray(t2[els].reshape(-1))
        vals = vals.at[tg].add(kflat[jnp.asarray(els)].reshape(-1))
    return vals


def scatter_colored(sched: AssemblySchedule, ke, variant: str = "stream",
                    interpret=None) -> jnp.ndarray:
    """Per-color batched conflict-free scatter-add: inside one color every
    target index is unique (no two elements share a DOF), so each color
    batch is a permutation write — the colorful path's execution
    discipline applied to assembly.  Executed by the fused colored-batch
    kernels (``variant`` in {'stream', 'onehot'}, dispatched like the
    SpMV variants) over the schedule's precomputed (C, Lmax) packs;
    ``variant='percolor'`` selects the legacy one-scatter-per-color
    baseline.  jit-compatible (the packs are static per schedule)."""
    if variant == "percolor":
        return scatter_colored_percolor(sched, ke)
    return akern.colored_scatter(
        sched.color_slots, sched.color_targets,
        jnp.asarray(ke, jnp.float32), sched.size,
        variant=variant, interpret=interpret)


def scatter_sorted(sched: AssemblySchedule, ke) -> jnp.ndarray:
    """Sorted-slot assembly (arXiv:2012.00585 analogue): contributions
    were argsorted by destination at schedule-build time, so the refresh
    is one color-free gather + monotone segment-sum — a single fused
    launch with no palette term.  jit-compatible."""
    return akern.sorted_scatter(
        sched.sorted_perm, sched.sorted_targets,
        jnp.asarray(ke, jnp.float32), sched.size)


def scatter_private(sched: AssemblySchedule, ke) -> jnp.ndarray:
    """Private-buffer accumulation: each buffer scatter-adds its element
    chunk into its own full-length partial (duplicates within a buffer are
    fine — the buffer is private), then the partials are reduced — the
    paper's local-buffers / all-in-one strategy (§3.1) as a vmap +
    tree-sum.  Padded slots target a dump entry past the vector end."""
    kflat = jnp.asarray(ke, jnp.float32).reshape(sched.ne, -1)
    t2 = jnp.asarray(sched.targets.reshape(sched.ne, -1))
    be = jnp.asarray(sched.buffer_elements)             # (B, epb)
    valid = (be >= 0)[..., None]
    el = jnp.maximum(be, 0)
    v3 = jnp.where(valid, kflat[el], 0.0)               # (B, epb, edof²)
    t3 = jnp.where(valid, t2[el], sched.size)           # dump slot

    def one_buffer(tg, vv):
        return jnp.zeros(sched.size + 1, jnp.float32).at[
            tg.reshape(-1)].add(vv.reshape(-1))

    partials = jax.vmap(one_buffer)(t3, v3)             # (B, size+1)
    return partials.sum(axis=0)[:sched.size]


def scatter_serial(sched: AssemblySchedule, ke) -> np.ndarray:
    """Serial numpy oracle: element-order ``np.add.at`` — the ground truth
    the parallel strategies must reproduce (bit-for-bit with the dyadic
    stiffness synthesis)."""
    vals = np.zeros(sched.size, np.float32)
    np.add.at(vals, np.asarray(sched.targets),
              np.asarray(ke, np.float32).reshape(-1))
    return vals


def values_to_csrc(sched: AssemblySchedule, vals) -> csrc.CSRC:
    """Split the unified value vector back into (ad, al, au) and wrap the
    schedule's structure — the O(k) value-refresh constructor.  Device
    values come to the host and go back up as the matrix's streams."""
    with obs.span("assembly.to_csrc"):
        vals = np.asarray(to_host(vals, "to_csrc"), np.float32)
        n, k = sched.n, sched.k
        return csrc.from_assembly(n, sched.ia, sched.ja,
                                  vals[:n], vals[n:n + k], vals[n + k:])


def assemble(sched: AssemblySchedule, ke, strategy: str = "colored",
             variant: Optional[str] = None,
             interpret=None) -> csrc.CSRC:
    """Assemble the global CSRC matrix from per-element dense blocks
    ``ke`` of shape (ne, edof, edof) with the chosen accumulation
    strategy.

    This IS the value-refresh fast path: every call reuses the
    schedule's precomputed packs (zero structural work — the
    ``BUILD_COUNTS['assembly_value_refresh']`` probe counts exactly one
    refresh per call and nothing else moves), runs under an obs span,
    and lands its wall time in ``assembly_scatter_seconds{strategy,
    variant}``."""
    if strategy not in STRATEGIES:
        raise ValueError(f"strategy {strategy!r} not in {STRATEGIES}")
    variant = _DEFAULT_VARIANT[strategy] if variant is None else variant
    t0 = time.perf_counter()
    with obs.span("assembly.value_refresh", strategy=strategy,
                  variant=variant):
        with obs.span("assembly.scatter"):
            if strategy == "colored":
                vals = scatter_colored(sched, ke, variant=variant,
                                       interpret=interpret)
            elif strategy == "sorted":
                vals = scatter_sorted(sched, ke)
            elif strategy == "private":
                vals = scatter_private(sched, ke)
            else:
                vals = scatter_serial(sched, ke)
            # wait here, so the scatter's device work stays in its span
            # and ``assembly.to_csrc`` times the round trip alone
            vals = jax.block_until_ready(vals)
        M = values_to_csrc(sched, vals)
    BUILD_COUNTS.inc("assembly_value_refresh")
    obs.histogram("assembly_scatter_seconds", strategy=strategy,
                  variant=variant).observe(time.perf_counter() - t0)
    return M


def assemble_mesh(mesh: Mesh, ke, ndof_per_node: int = 1,
                  strategy: str = "colored", cache=None,
                  num_buffers: int = 8,
                  coloring_provider: str = "greedy"):
    """One-call mesh → CSRC assembly; returns (matrix, schedule) so
    repeated value refreshes reuse the schedule (or pass ``cache=`` and
    the connectivity digest does it for you)."""
    sched = assembly_schedule_for(mesh, ndof_per_node=ndof_per_node,
                                  num_buffers=num_buffers, cache=cache,
                                  coloring_provider=coloring_provider)
    return assemble(sched, ke, strategy=strategy), sched


# ---------------------------------------------------------------------------
# Predict-then-measure strategy selection (the assembly tuner path)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AssemblyTuneResult:
    """Winner of one assembly strategy tune (mirrors tuner.TuneResult)."""
    strategy: str
    variant: str
    timings_s: Dict[str, float]        # "strategy/variant" -> measured s
    predictions_s: Dict[str, float]    # every priced candidate
    # measured set: least device time / measured (None off the peak table)
    roofline_fraction: Dict[str, Optional[float]]
    cached: bool                       # True = PlanCache hit, nothing timed

    def key(self) -> str:
        return f"{self.strategy}/{self.variant}"


def _scatter_fn(sched: AssemblySchedule, strategy: str, variant: str):
    """The jitted value-refresh executor of one candidate (the schedule's
    packs are program arguments, not baked-in constants)."""
    if strategy == "colored":
        return jit_hoisted(lambda k: scatter_colored(sched, k,
                                                     variant=variant))
    if strategy == "sorted":
        return jit_hoisted(lambda k: scatter_sorted(sched, k))
    if strategy == "private":
        return jit_hoisted(lambda k: scatter_private(sched, k))
    raise ValueError(f"no tunable executor for strategy {strategy!r}")


def _time_scatter(fn, kej, warmup: int = 2, repeats: int = 5) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(kej))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(kej))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def tune_assembly(sched: AssemblySchedule, ke, cache=None,
                  measure=None, repeats: int = 5,
                  force: bool = False) -> AssemblyTuneResult:
    """Pick the assembly (strategy, variant) for this schedule: price the
    whole candidate pool with the roofline model, measure the cheapest
    half (plus each strategy's best-predicted variant, so no family is
    pruned unseen), argmin, and record predicted-vs-measured provenance.

    The winner persists in the PlanCache under ``asmplan-<schedule key>``
    — a later call with the same cache returns it without timing
    anything.  ``measure(fn, ke)`` is injectable for deterministic
    tests."""
    from repro.roofline import cost_model

    plan_key = "asmplan-" + sched.key()
    if cache is not None and not force:
        hit = cache.get_assembly_plan(plan_key)
        if hit is not None:
            return AssemblyTuneResult(
                strategy=hit["strategy"], variant=hit["variant"],
                timings_s=dict(hit.get("timings_s", {})),
                predictions_s=dict(hit.get("predictions_s", {})),
                roofline_fraction=dict(hit.get("roofline_fraction", {})),
                cached=True)

    priced = cost_model.rank_assembly_candidates(sched,
                                                 ASSEMBLY_CANDIDATES)
    predictions = {f"{s}/{v}": est.predicted_s for (s, v), est in priced}
    ests = {f"{s}/{v}": est for (s, v), est in priced}
    obs.counter("assembly_tuner_candidates_total",
                outcome="enumerated").inc(len(priced))

    pool = [sv for sv, _ in priced]
    chosen = list(pool[:max(2, len(pool) // 2)])
    seen_strategies = {s for s, _ in chosen}
    for s, v in pool:                  # best-predicted variant per family
        if s not in seen_strategies:
            chosen.append((s, v))
            seen_strategies.add(s)

    kej = jnp.asarray(np.asarray(ke, np.float32))
    timings: Dict[str, float] = {}
    for s, v in chosen:
        fn = _scatter_fn(sched, s, v)
        t = (measure(fn, kej) if measure is not None
             else _time_scatter(fn, kej, repeats=repeats))
        timings[f"{s}/{v}"] = float(t)
    obs.counter("assembly_tuner_candidates_total",
                outcome="measured").inc(len(timings))

    winner = min(timings, key=timings.get)
    kind = jax.devices()[0].device_kind
    fractions = {key: cost_model.roofline_fraction(ests[key], t, kind)
                 for key, t in timings.items() if t > 0}
    ws, wv = winner.split("/")

    result = AssemblyTuneResult(
        strategy=ws, variant=wv, timings_s=timings,
        predictions_s=predictions, roofline_fraction=fractions,
        cached=False)
    if cache is not None:
        cache.put_assembly_plan(plan_key, {
            "strategy": ws, "variant": wv, "timings_s": timings,
            "predictions_s": predictions,
            "roofline_fraction": fractions})
    return result
