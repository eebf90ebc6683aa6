"""The unified SpMV schedule layer: every structure-dependent precomputation
an :class:`~repro.core.plan.ExecutionPlan` needs to execute, bundled in one
cached, serializable artifact.

The paper's two race-avoidance families — per-thread buffers with four
accumulation variants (§3.1) and conflict-graph coloring (§3.2) — are all
*precomputations over the matrix structure*.  Before this layer each consumer
rebuilt its own piece ad-hoc (the operator packed block-ELL inline, the
distributed builders re-derived partitions and halo windows, the colorful
path re-ran the greedy colorer).  ``SpmvSchedule`` gives them one home:

  partition        nnz-guided (or row-count) :class:`RowPartition` with the
                   paper's *effective* write ranges per part
  halo             per-part halo widths (§3.1 effective accumulation;
                   the distributed 'halo' strategy's exchange windows)
  pack             the block-ELL pack for the Pallas kernel path
  coloring         balanced largest-degree-first :class:`Coloring` plus
                   device-ready per-color slot batches (colorful path)

A schedule is built **once** per (matrix fingerprint, value digest, plan,
partition width) and stored next to the plan in the tuner's
:class:`~repro.core.tuner.PlanCache` — a serving process that re-registers a
known matrix performs zero pack/partition/coloring work
(``BUILD_COUNTS`` is the probe tests assert that with).

Path-specific artifact contents (the block-ELL pack, the flat-grid pack,
the coloring batches) are built and serialized by the path's
:class:`~repro.core.paths.KernelPath` registry entry — this module owns
the common pieces (partition, halo, fingerprinting, cache plumbing) and
delegates the rest, so a newly registered path is schedule-cached with
zero edits here.

Serialization is npz + a JSON meta record (``save_npz`` / ``load_npz``);
``SCHEDULE_VERSION`` gates the on-disk layout — bumping it (e.g. on a pack
format change) invalidates every stored schedule, which is then silently
rebuilt on the next request.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Optional

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.runtime import to_host
from . import paths as paths_mod
from .blockell import BlockEll
from .coloring import Coloring
from .csrc import CSRC, memo_digest, row_of_slot
from .partition import (RowPartition, halo_widths, partition_rows_by_count,
                        partition_rows_by_nnz)
# the build probe lives with the registry (path builders count into it);
# re-exported here because consumers/tests address it as
# ``schedule.BUILD_COUNTS`` — same Counter object.
from .paths import BUILD_COUNTS
from .plan import ExecutionPlan

# version 5: the 'nnzsplit' path's NnzSplitPack artifact joins the npz
# layout (nnzsplit_* arrays + "nnzsplit_pack" meta).  Version-4 files
# load as misses and are rebuilt transparently.
# version 6: the colorful artifact records its coloring provider plus the
# RACE level-group metadata (color_level_of_row / color_group_of_row), and
# the provider joins the colorful path's artifact fields (schedule keys).
# Version-5 files load as misses and are rebuilt transparently.
SCHEDULE_VERSION = 6


@dataclasses.dataclass(frozen=True)
class SpmvSchedule:
    """Everything structure-dependent one plan needs to execute one matrix."""

    fingerprint: str            # matrix-class key (tuner.fingerprint)
    value_digest: str           # exact structure+values digest (this matrix)
    plan: ExecutionPlan
    n: int
    m: int
    p: int                      # partition width the row partition was built for
    partition: RowPartition
    halo: np.ndarray            # (p,) halo width per part (effective ranges)
    # --- path-specific artifact fields (built/serialized by the path's
    # KernelPath registry entry; exactly the fields its build_artifact
    # returns are non-None) ---
    pack: Optional[BlockEll] = None          # 'kernel' path
    coloring: Optional[Coloring] = None      # 'colorful' path
    # device-ready color batches: slot ids grouped by color, concatenated;
    # color c owns color_slots[color_slot_ptr[c]:color_slot_ptr[c+1]].
    color_slots: Optional[np.ndarray] = None
    color_slot_ptr: Optional[np.ndarray] = None
    flat_pack: Optional[object] = None       # 'flat' path (FlatBlockEll)
    nnzsplit_pack: Optional[object] = None   # 'nnzsplit' path (NnzSplitPack)
    ell_pack: Optional[object] = None        # 'ell' path (EllPack)
    # exact-structure digest (ia/ja/iar/jar only — values excluded): the
    # key of the value-refresh fast path (refresh_schedule)
    structure_digest: str = ""

    def key(self) -> str:
        return schedule_key(self.fingerprint, self.value_digest, self.plan,
                            self.p)

    # ------------------------------------------------------------------
    # Serialization (npz arrays + JSON meta); the path-specific section is
    # delegated to the registry entry's save_artifact/load_artifact
    # ------------------------------------------------------------------

    def save_npz(self, path: str):
        meta = {
            "version": SCHEDULE_VERSION,
            "fingerprint": self.fingerprint,
            "value_digest": self.value_digest,
            "plan": self.plan.to_dict(),
            "n": self.n, "m": self.m, "p": self.p,
            "structure_digest": self.structure_digest,
        }
        arrays = {
            "part_starts": np.asarray(self.partition.starts),
            "part_eff_lo": np.asarray(self.partition.eff_lo),
            "part_eff_hi": np.asarray(self.partition.eff_hi),
            "part_nnz": np.asarray(self.partition.nnz_per_part),
            "halo": np.asarray(self.halo),
        }
        entry = paths_mod.get_path(self.plan.path)
        path_meta, path_arrays = entry.save_artifact(self)
        meta.update(path_meta)
        arrays.update(path_arrays)
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp.npz"
        with open(tmp, "wb") as f:
            np.savez_compressed(f, __meta__=np.frombuffer(
                json.dumps(meta, sort_keys=True).encode(), dtype=np.uint8),
                **arrays)
        os.replace(tmp, path)

    @classmethod
    def load_npz(cls, path: str) -> "SpmvSchedule":
        with np.load(path) as z:
            meta = json.loads(bytes(z["__meta__"]).decode())
            if meta.get("version") != SCHEDULE_VERSION:
                raise ValueError(
                    f"schedule {path}: version {meta.get('version')!r} "
                    f"!= {SCHEDULE_VERSION}")
            plan = ExecutionPlan.from_dict(meta["plan"])
            part = RowPartition(starts=z["part_starts"],
                                eff_lo=z["part_eff_lo"],
                                eff_hi=z["part_eff_hi"],
                                nnz_per_part=z["part_nnz"])
            entry = paths_mod.get_path(plan.path)
            fields = entry.load_artifact(meta, z)
            return cls(fingerprint=meta["fingerprint"],
                       value_digest=meta["value_digest"], plan=plan,
                       n=meta["n"], m=meta["m"], p=meta["p"],
                       structure_digest=meta["structure_digest"],
                       partition=part, halo=z["halo"], **fields)


def value_digest(M: CSRC) -> str:
    """Digest of the exact matrix content (structure AND values).

    The tuner's ``fingerprint`` identifies a matrix *class* (two matrices of
    the same generator share it, so plans transfer).  A schedule embeds the
    matrix values (pack value streams, per-slot al/au), so its cache key
    additionally pins the exact matrix — a same-class matrix with different
    values rebuilds instead of silently reusing another matrix's values.
    Computed once per device-backed instance (``csrc.memo_digest``).
    """
    return memo_digest(M, "value", _value_digest)


def _value_digest(M: CSRC) -> str:
    h = hashlib.sha1()
    with obs.span("schedule.value_digest"):
        for a in (M.ia, M.ja, M.ad, M.al, M.au, M.iar, M.jar, M.ar):
            arr = np.ascontiguousarray(to_host(a, "value_digest"))
            h.update(arr.tobytes())
    return h.hexdigest()[:16]


def structure_digest(M: CSRC) -> str:
    """Digest of the matrix *structure* only (ia/ja/iar/jar + shape).

    Two matrices sharing it differ at most in values — the FEM
    time-stepping shape (re-assembled stiffness on a fixed mesh).  For
    such a pair every structural schedule artifact (partition, halo,
    coloring, pack index streams) is identical; only the value streams
    need refreshing (:func:`refresh_schedule`).  Computed once per
    device-backed instance (``csrc.memo_digest``).
    """
    return memo_digest(M, "structure", _structure_digest)


def _structure_digest(M: CSRC) -> str:
    h = hashlib.sha1()
    h.update(np.asarray([M.n, M.m], np.int64).tobytes())
    with obs.span("schedule.structure_digest"):
        for a in (M.ia, M.ja, M.iar, M.jar):
            h.update(np.ascontiguousarray(
                to_host(a, "structure_digest")).tobytes())
    return h.hexdigest()[:16]


def plan_artifact_fields(plan: ExecutionPlan) -> tuple:
    """The plan fields the schedule artifact actually depends on.  Two plans
    differing only in accumulation strategy or tuned RHS width (nrhs) share
    one artifact — the pack/partition/coloring are identical.  The
    path-specific tail comes from the registry entry ('kernel'/'flat' pin
    their tile/window geometry; 'segment'/'colorful' add nothing)."""
    entry = paths_mod.get_path(plan.path)
    return (plan.path, plan.partition) + tuple(entry.artifact_fields(plan))


def schedule_key(fingerprint: str, digest: str, plan: ExecutionPlan,
                 p: int) -> str:
    ph = hashlib.sha1(json.dumps(plan_artifact_fields(plan)).encode()
                      ).hexdigest()[:10]
    return f"{fingerprint}.{digest}.p{p}.{ph}"


def color_slot_batches(M: CSRC, coloring: Coloring):
    """Device-ready colorful batches: lower-triangle slot ids grouped by the
    color of their owning row (the per-color gather/scatter index sets the
    colorful path replays serially).  Returns (slots, ptr)."""
    ia = np.asarray(M.ia)
    slots = []
    ptr = np.zeros(coloring.num_colors + 1, dtype=np.int64)
    for c in range(coloring.num_colors):
        rows = coloring.rows(c)
        sl = (np.concatenate([np.arange(ia[r], ia[r + 1]) for r in rows])
              if len(rows) else np.zeros(0, np.int64))
        slots.append(sl.astype(np.int32))
        ptr[c + 1] = ptr[c] + sl.shape[0]
    slots = (np.concatenate(slots).astype(np.int32) if slots
             else np.zeros(0, np.int32))
    return slots, ptr


def build_schedule(M: CSRC, plan: ExecutionPlan, p: int = 8,
                   coloring: Optional[Coloring] = None) -> SpmvSchedule:
    """Build the full schedule artifact for (matrix, plan).

    The path-specific artifact (pack / flat pack / coloring batches) comes
    from the plan path's registry entry; it raises ValueError exactly where
    strict plan execution must fail: a windowed ('kernel'/'flat') plan
    whose window exceeds ``plan.w_cap`` (bandwidth gate) and square-only
    plans on rectangular matrices.
    """
    from .tuner import fingerprint as _fingerprint   # local: avoid cycle

    entry = paths_mod.get_path(plan.path)
    # build the path artifact first: infeasible plans raise before any
    # build counter moves
    with obs.span("schedule.build_artifact", path=plan.path):
        fields = entry.build_artifact(M, plan, coloring=coloring)

    BUILD_COUNTS.inc("schedule")
    BUILD_COUNTS.inc("partition")
    with obs.span("schedule.partition", partition=plan.partition):
        p = max(1, min(p, M.n))
        if plan.partition == "count":
            part = partition_rows_by_count(M, p)
        else:
            part = partition_rows_by_nnz(M, p)
        halo = np.asarray(halo_widths(part), dtype=np.int64)

    return SpmvSchedule(
        fingerprint=_fingerprint(M), value_digest=value_digest(M),
        plan=plan, n=M.n, m=M.m, p=p, partition=part, halo=halo,
        structure_digest=structure_digest(M), **fields)


def refresh_schedule(sched: SpmvSchedule, M: CSRC) -> SpmvSchedule:
    """Same-structure value refresh: a new schedule for ``M`` reusing every
    structural artifact of ``sched`` (partition, halo, coloring, pack index
    streams) and rebuilding only the value streams.

    This is the FEM time-stepping fast path — the matrix is re-assembled
    every step with unchanged connectivity, so re-packing or re-coloring
    would redo O(nnz) structural work per step for nothing.  The path's
    registry entry supplies the stream refresh ('kernel'/'flat' refill the
    pack values vectorized); paths whose artifacts are purely structural
    ('segment', 'colorful' — executors read values from ``M`` directly)
    reuse the artifact as-is.  Raises ValueError when the structures do
    not actually match.
    """
    with obs.span("schedule.value_refresh"):
        if structure_digest(M) != sched.structure_digest:
            raise ValueError(
                "refresh_schedule: matrix structure differs from the "
                "schedule's; a full rebuild (build_schedule) is required")
        entry = paths_mod.get_path(sched.plan.path)
        BUILD_COUNTS.inc("value_refresh")
        fields = ({} if entry.refresh_values is None
                  else entry.refresh_values(M, sched))
        return dataclasses.replace(sched, value_digest=value_digest(M),
                                   **fields)


def schedule_for(M: CSRC, plan: ExecutionPlan, cache=None, p: int = 8,
                 coloring: Optional[Coloring] = None) -> SpmvSchedule:
    """The schedule to execute (M, plan) with — cache hit wins.

    ``cache`` is a :class:`~repro.core.tuner.PlanCache`; a hit performs zero
    pack/partition/coloring work.  On a value-digest miss a same-structure
    schedule (matching fingerprint + structure digest — FEM time stepping)
    is value-refreshed instead of rebuilt (:func:`refresh_schedule`): only
    the value streams are touched, no re-pack/re-partition/re-color.  An
    explicit ``coloring`` override bypasses the cache (custom colorings are
    caller-owned, not shared artifacts).
    """
    from .tuner import fingerprint as _fingerprint

    with obs.span("schedule.resolve"):
        if coloring is not None or cache is None:
            return build_schedule(M, plan, p=p, coloring=coloring)
        fp = _fingerprint(M)
        vd = value_digest(M)
        hit = cache.get_schedule(fp, vd, plan, p)
        if hit is not None:
            return hit
        base = cache.find_schedule_by_structure(fp, structure_digest(M),
                                                plan, p)
        if base is not None:
            obs.counter("plan_cache_lookups_total", kind="schedule",
                        outcome="refresh").inc()
            sched = refresh_schedule(base, M)
            # the refreshed generation supersedes the base in memory (one
            # schedule per structure, not one per step); the npz already
            # on disk keeps serving fresh processes, so skip
            # re-compressing a full artifact per time step
            cache.drop_schedule(base, remove_file=False)
            cache.put_schedule(sched, persist=False)
        else:
            sched = build_schedule(M, plan, p=p)
            cache.put_schedule(sched)
        return sched


# ---------------------------------------------------------------------------
# Distributed slot layouts (the shard-level structure precomputations the
# core/distributed.py strategies execute with)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedSlots:
    """Slot arrays split into p nnz-balanced groups, padded to equal length
    and stacked on a leading shard axis (allreduce/reduce_scatter)."""
    row_idx: jnp.ndarray     # (p, S) global row of each slot (pad: 0)
    ja: jnp.ndarray          # (p, S) global col             (pad: 0)
    al: jnp.ndarray          # (p, S)                        (pad: 0.0)
    au: jnp.ndarray          # (p, S)
    ad_shard: jnp.ndarray    # (p, n) diagonal owned by shard (zero elsewhere)
    part: RowPartition


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


# Memo for the device-ready distributed layouts: repeated builder calls for
# the same matrix (serving restarts, solver re-instantiation) are
# zero-precompute, matching the schedule-cache contract.  Keys pin the exact
# matrix (value digest) and the layout geometry; entries are small (device
# array handles) and matrices served per process are few, so no eviction.
_SHARDED_SLOTS_MEMO: dict = {}
_HALO_LAYOUT_MEMO: dict = {}


# ---------------------------------------------------------------------------
# Shard-layout (de)serialization: the npz layer that ships per-shard
# sub-artifacts (ShardedSlots / HaloLayout and every registered path's
# ShardSupport layouts) to serving workers through the PlanCache, keyed
# by (fingerprint, value digest, p, strategy kind, pack geometry).
# ---------------------------------------------------------------------------

SHARD_LAYOUT_VERSION = 1


def _layout_kinds() -> dict:
    """npz-kind -> dataclass for every serializable shard layout: the two
    segment-path layouts owned here, plus every registered path's
    ShardSupport layouts (the registry keeps this map current — a new
    path's layouts serialize with zero edits here)."""
    kinds = {"sharded_slots": ShardedSlots, "halo": HaloLayout}
    for entry in paths_mod.registered_paths():
        if entry.shard_support is not None:
            kinds.update(entry.shard_support.layout_classes())
    return kinds


def shard_layout_key(kind: str, fp: str, digest: str, p: int,
                     geo: tuple = ()) -> str:
    """Cache key of one distributed layout: matrix class + exact values +
    shard count + strategy family, plus a hash of the pack geometry (tile
    height, k-step, index dtype, partition boundaries...)."""
    gh = hashlib.sha1(json.dumps([str(g) for g in geo]).encode()
                      ).hexdigest()[:10]
    return f"shard-{kind}-{fp}.{digest}.p{p}.{gh}"


def save_shard_layout_npz(path: str, lay):
    """Serialize any registered shard-layout dataclass: scalar fields go
    to the JSON meta, arrays (and the embedded RowPartition) to npz, and
    the names of absent (None) arrays to the meta.  bf16 value streams
    persist widened to f32 (lossless) and re-narrow on load (npz has no
    native bfloat16)."""
    kinds = _layout_kinds()
    kind = next(k for k, cls in kinds.items() if isinstance(lay, cls))
    meta = {"version": SHARD_LAYOUT_VERSION, "kind": kind}
    arrays = {}
    for f in dataclasses.fields(lay):
        v = getattr(lay, f.name)
        if isinstance(v, RowPartition):
            for pf in dataclasses.fields(v):
                arrays[f"part__{pf.name}"] = np.asarray(getattr(v, pf.name))
        elif isinstance(v, (bool, int, float)):
            meta[f.name] = v
        elif v is None:
            meta.setdefault("__none__", []).append(f.name)
        elif str(v.dtype) == "bfloat16":
            meta.setdefault("__bf16__", []).append(f.name)
            arrays[f.name] = np.asarray(v, dtype=np.float32)
        else:
            arrays[f.name] = np.asarray(v)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp.npz"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, __meta__=np.frombuffer(
            json.dumps(meta, sort_keys=True).encode(), dtype=np.uint8),
            **arrays)
    os.replace(tmp, path)


def load_shard_layout_npz(path: str):
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"]).decode())
        if meta.get("version") != SHARD_LAYOUT_VERSION:
            raise ValueError(
                f"shard layout {path}: version {meta.get('version')!r} "
                f"!= {SHARD_LAYOUT_VERSION}")
        cls = _layout_kinds()[meta["kind"]]
        bf16 = set(meta.get("__bf16__", ()))
        kwargs = {}
        for f in dataclasses.fields(cls):
            if f.name in meta:
                kwargs[f.name] = meta[f.name]
            elif f.name in meta.get("__none__", ()):
                kwargs[f.name] = None
            elif f.name == "part":
                kwargs["part"] = RowPartition(
                    starts=z["part__starts"], eff_lo=z["part__eff_lo"],
                    eff_hi=z["part__eff_hi"],
                    nnz_per_part=z["part__nnz_per_part"])
            else:
                kwargs[f.name] = jnp.asarray(
                    z[f.name],
                    dtype=jnp.bfloat16 if f.name in bf16 else None)
        return cls(**kwargs)


def _cached_layout(M: CSRC, cache, kind: str, p: int, geo: tuple):
    """Probe the cache's shipped-artifact store for a layout; returns
    (layout_or_None, key_or_None)."""
    if cache is None:
        return None, None
    from .tuner import fingerprint as _fingerprint
    key = shard_layout_key(kind, _fingerprint(M), value_digest(M), p, geo)
    return cache.get_shard_layout(key), key


def _ensure_shipped(M: CSRC, cache, kind: str, p: int, geo: tuple, lay):
    """Persist a memoized layout on the first cache-bearing request: a
    layout built without a cache (e.g. during tune_mesh measurement)
    ships as soon as a cache-aware consumer asks for it."""
    if cache is None:
        return
    shipped, key = _cached_layout(M, cache, kind, p, geo)
    if shipped is None and key is not None:
        cache.put_shard_layout(key, lay)


def build_sharded_slots(M: CSRC, part: RowPartition,
                        cache=None) -> ShardedSlots:
    """Shard-stacked slot arrays over the schedule's row partition
    (memoized per exact matrix + partition boundaries; with ``cache``,
    also served from / shipped to the PlanCache npz layer)."""
    starts_geo = tuple(int(s) for s in np.asarray(part.starts))
    memo_key = (value_digest(M), np.asarray(part.starts).tobytes())
    hit = _SHARDED_SLOTS_MEMO.get(memo_key)
    if hit is not None:
        _ensure_shipped(M, cache, "sharded_slots", part.p, starts_geo, hit)
        return hit
    shipped, key = _cached_layout(M, cache, "sharded_slots", part.p,
                                  starts_geo)
    if shipped is not None:
        _SHARDED_SLOTS_MEMO[memo_key] = shipped
        return shipped
    BUILD_COUNTS.inc("sharded_slots")
    p = part.p
    ros = row_of_slot(M)
    ja = np.asarray(M.ja)
    al = np.asarray(M.al)
    au = np.asarray(M.au)
    ia = np.asarray(M.ia)
    spans = [(int(ia[part.starts[t]]), int(ia[part.starts[t + 1]]))
             for t in range(p)]
    smax = max(1, max(e - s for s, e in spans))
    smax = _round_up(smax, 128)

    def padded(arr, fill, dtype):
        out = np.full((p, smax), fill, dtype=dtype)
        for t, (s, e) in enumerate(spans):
            out[t, :e - s] = arr[s:e]
        return jnp.asarray(out)

    ad_shard = np.zeros((p, M.n), dtype=np.float32)
    for t in range(p):
        r0, r1 = part.rows(t)
        ad_shard[t, r0:r1] = np.asarray(M.ad)[r0:r1]

    out = ShardedSlots(
        row_idx=padded(ros, 0, np.int32),
        ja=padded(ja, 0, np.int32),
        al=padded(al, 0.0, np.float32),
        au=padded(au, 0.0, np.float32),
        ad_shard=jnp.asarray(ad_shard),
        part=part,
    )
    _SHARDED_SLOTS_MEMO[memo_key] = out
    if key is not None:
        cache.put_shard_layout(key, out)
    return out


@dataclasses.dataclass(frozen=True)
class HaloLayout:
    """Equal-row shard slot arrays in *local* coordinates for the paper's
    effective-accumulation ('halo') strategy: each shard owns ns rows and
    writes at most h rows below its range (the halo exchanged with the left
    neighbor)."""
    p: int
    ns: int                  # rows per shard (8-aligned)
    h: int                   # halo width (8-aligned bandwidth)
    n_pad: int
    row_loc: jnp.ndarray     # (p, S) local row of each slot
    col_rel: jnp.ndarray     # (p, S) column relative to [r0-h, r1)
    al: jnp.ndarray          # (p, S)
    au: jnp.ndarray          # (p, S)
    ad: jnp.ndarray          # (p, ns)


def build_halo_layout(M: CSRC, p: int, cache=None) -> HaloLayout:
    """Memoized per exact matrix + shard count (with ``cache``, also
    served from / shipped to the PlanCache npz layer).  Raises ValueError
    when the band does not fit inside one shard (the strategy's
    feasibility gate — callers fall back to allreduce/reduce_scatter)."""
    from .csrc import bandwidth as csrc_bandwidth

    memo_key = (value_digest(M), p)
    hit = _HALO_LAYOUT_MEMO.get(memo_key)
    if hit is not None:
        _ensure_shipped(M, cache, "halo", p, (), hit)
        return hit
    shipped, key = _cached_layout(M, cache, "halo", p, ())
    if shipped is not None:
        _HALO_LAYOUT_MEMO[memo_key] = shipped
        return shipped
    BUILD_COUNTS.inc("halo_layout")
    n = M.n
    ns = _round_up(-(-n // p), 8)          # rows per shard
    n_pad = ns * p
    band = csrc_bandwidth(M)
    h = max(8, _round_up(band, 8))
    if h > ns:
        raise ValueError(
            f"band {band} exceeds shard rows {ns}; halo strategy needs "
            "band <= n/p (fall back to allreduce/reduce_scatter)")

    ros = row_of_slot(M)
    ja = np.asarray(M.ja)
    al_np = np.asarray(M.al)
    au_np = np.asarray(M.au)
    shard_of_slot = ros // ns
    counts = np.bincount(shard_of_slot, minlength=p)
    smax = _round_up(max(1, int(counts.max())), 128)
    row_loc = np.zeros((p, smax), np.int32)
    col_rel = np.full((p, smax), ns + h - 1, np.int32)   # inert target
    al_s = np.zeros((p, smax), np.float32)
    au_s = np.zeros((p, smax), np.float32)
    # each shard's slots in slot order, at consecutive places of its row
    order = np.argsort(shard_of_slot, kind="stable")
    t = shard_of_slot[order]
    q = np.arange(order.size) - (np.cumsum(counts) - counts)[t]
    row_loc[t, q] = ros[order] - t * ns
    col_rel[t, q] = ja[order] - (t * ns - h)             # in [0, ns+h)
    al_s[t, q] = al_np[order]
    au_s[t, q] = au_np[order]
    ad_pad = np.zeros(n_pad, np.float32)
    ad_pad[:n] = np.asarray(M.ad)
    out = HaloLayout(p=p, ns=ns, h=h, n_pad=n_pad,
                     row_loc=jnp.asarray(row_loc),
                     col_rel=jnp.asarray(col_rel),
                     al=jnp.asarray(al_s), au=jnp.asarray(au_s),
                     ad=jnp.asarray(ad_pad.reshape(p, ns)))
    _HALO_LAYOUT_MEMO[memo_key] = out
    if key is not None:
        cache.put_shard_layout(key, out)
    return out


# Shard-local path layouts (a plan whose path has ShardSupport, under a
# distributed strategy): per-shard sub-packs, memoized like the other
# layouts so repeated builder calls are zero-precompute.  One memo dict
# per layout kind; the flat names are module-level for compatibility
# (tests clear them to force rebuild counting).
_FLAT_SHARDS_MEMO: dict = {}
_FLAT_HALO_MEMO: dict = {}
_PATH_LAYOUT_MEMOS: dict = {"flat_shards": _FLAT_SHARDS_MEMO,
                            "flat_halo": _FLAT_HALO_MEMO}


def _layout_memo(kind: str) -> dict:
    return _PATH_LAYOUT_MEMOS.setdefault(kind, {})


# one mapping from plan dtype strings to jnp dtypes for the whole stack
# (paths.py owns it; the local pack builders use the same helpers)
_plan_index_dtype = paths_mod._index_dtype_of
_plan_value_dtype = paths_mod._value_dtype_of


def _shard_support_of(path_name: str):
    sup = paths_mod.get_path(path_name).shard_support
    if sup is None:
        raise ValueError(f"path {path_name!r} registers no shard support; "
                         "distributed strategies run it as segment-sum")
    return sup


def build_path_shards(M: CSRC, part: RowPartition, plan: ExecutionPlan,
                      cache=None):
    """Per-shard sub-packs of ``plan.path`` over the schedule's row
    partition (global coordinates; allreduce / reduce_scatter
    strategies).  Generic over the registry's ShardSupport: memoized per
    exact matrix + partition boundaries + path pack geometry; with
    ``cache``, also served from / shipped to the PlanCache npz layer."""
    sup = _shard_support_of(plan.path)
    kind = sup.shards_kind
    pgeo = sup.geometry(plan)
    geo = pgeo + tuple(int(s) for s in np.asarray(part.starts))
    memo = _layout_memo(kind)
    memo_key = (value_digest(M), np.asarray(part.starts).tobytes()) + pgeo
    hit = memo.get(memo_key)
    if hit is not None:
        _ensure_shipped(M, cache, kind, part.p, geo, hit)
        return hit
    shipped, key = _cached_layout(M, cache, kind, part.p, geo)
    if shipped is not None:
        memo[memo_key] = shipped
        return shipped
    BUILD_COUNTS.inc(kind)
    out = sup.pack_shards(M, np.asarray(part.starts), plan)
    memo[memo_key] = out
    if key is not None:
        cache.put_shard_layout(key, out)
    return out


def build_path_halo(M: CSRC, p: int, plan: ExecutionPlan, cache=None):
    """Per-shard local-coordinate packs of ``plan.path`` for the halo
    strategy.  Raises ValueError when the band does not fit inside one
    shard (same gate as :func:`build_halo_layout`).  Memoized per exact
    matrix + shard count + path pack geometry; with ``cache``, also
    served from / shipped to the PlanCache npz layer."""
    sup = _shard_support_of(plan.path)
    kind = sup.halo_kind
    geo = sup.geometry(plan)
    memo = _layout_memo(kind)
    memo_key = (value_digest(M), p) + geo
    hit = memo.get(memo_key)
    if hit is not None:
        _ensure_shipped(M, cache, kind, p, geo, hit)
        return hit
    shipped, key = _cached_layout(M, cache, kind, p, geo)
    if shipped is not None:
        memo[memo_key] = shipped
        return shipped
    BUILD_COUNTS.inc(kind)
    out = sup.pack_halo(M, p, plan)
    memo[memo_key] = out
    if key is not None:
        cache.put_shard_layout(key, out)
    return out


def build_flat_shards(M: CSRC, part: RowPartition, plan: ExecutionPlan,
                      cache=None):
    """Back-compat name: :func:`build_path_shards` for a 'flat' plan."""
    return build_path_shards(M, part, plan, cache=cache)


def build_flat_halo_layout(M: CSRC, p: int, plan: ExecutionPlan,
                           cache=None):
    """Back-compat name: :func:`build_path_halo` for a 'flat' plan."""
    return build_path_halo(M, p, plan, cache=cache)


# ---------------------------------------------------------------------------
# Same-structure value refresh of the shard layouts (the mesh-path analog
# of refresh_schedule: serving update_values / FEM time stepping must not
# re-pack, re-partition, or re-color on the mesh)
# ---------------------------------------------------------------------------

def refresh_shard_layout(lay, M: CSRC, part: Optional[RowPartition] = None):
    """Refill a distributed layout's value streams from a same-structure
    matrix.  Structural arrays (slot indices, tile maps, halo geometry)
    are reused untouched; only al/au/ad streams are rewritten — the probe
    counter is ``shard_value_refresh``, and no structural counter moves.
    ``part`` is required for the partition-keyed shards layouts
    (FlatShards, NnzSplitShards, ... — they do not embed their partition
    boundaries)."""
    BUILD_COUNTS.inc("shard_value_refresh")
    if isinstance(lay, ShardedSlots):
        return _refresh_sharded_slots(lay, M)
    if isinstance(lay, HaloLayout):
        return _refresh_halo_layout(lay, M)
    # path-specific layouts: dispatch through the registry's ShardSupport
    for entry in paths_mod.registered_paths():
        sup = entry.shard_support
        if sup is None:
            continue
        classes = sup.layout_classes()
        if isinstance(lay, classes[sup.shards_kind]):
            if part is None:
                raise ValueError(
                    f"refresh_shard_layout: {type(lay).__name__} needs "
                    "the row partition it was built over")
            return sup.refresh_shards(lay, M, np.asarray(part.starts))
        if isinstance(lay, classes[sup.halo_kind]):
            return sup.refresh_halo(lay, M)
    raise TypeError(f"unknown shard layout {type(lay).__name__}")


def _refresh_sharded_slots(ss: ShardedSlots, M: CSRC) -> ShardedSlots:
    """Value-only refill of the stacked slot arrays: the spans are
    re-derived from the (unchanged) row pointers, so the padded layout is
    bit-compatible with the original build."""
    part = ss.part
    p = part.p
    ia = np.asarray(M.ia)
    al = np.asarray(M.al)
    au = np.asarray(M.au)
    smax = int(ss.al.shape[1])
    spans = [(int(ia[part.starts[t]]), int(ia[part.starts[t + 1]]))
             for t in range(p)]

    def padded(arr):
        out = np.zeros((p, smax), dtype=np.float32)
        for t, (s, e) in enumerate(spans):
            out[t, :e - s] = arr[s:e]
        return jnp.asarray(out)

    ad_shard = np.zeros((p, M.n), dtype=np.float32)
    for t in range(p):
        r0, r1 = part.rows(t)
        ad_shard[t, r0:r1] = np.asarray(M.ad)[r0:r1]
    return dataclasses.replace(ss, al=padded(al), au=padded(au),
                               ad_shard=jnp.asarray(ad_shard))


def _refresh_halo_layout(lay: HaloLayout, M: CSRC) -> HaloLayout:
    """Value-only refill of the local-coordinate halo arrays, vectorized:
    slots are row-major, so a shard's slots are consecutive and the
    original fill order (stable sort over a non-decreasing shard array)
    is the identity."""
    ros = row_of_slot(M)
    k = ros.shape[0]
    p, ns = lay.p, lay.ns
    smax = int(lay.al.shape[1])
    al_s = np.zeros((p, smax), np.float32)
    au_s = np.zeros((p, smax), np.float32)
    if k:
        shard = ros // ns
        first = np.searchsorted(shard, np.arange(p))
        q = np.arange(k) - first[shard]
        al_s[shard, q] = np.asarray(M.al)
        au_s[shard, q] = np.asarray(M.au)
    ad_pad = np.zeros(lay.n_pad, np.float32)
    ad_pad[:M.n] = np.asarray(M.ad)
    return dataclasses.replace(lay, al=jnp.asarray(al_s),
                               au=jnp.asarray(au_s),
                               ad=jnp.asarray(ad_pad.reshape(p, ns)))


# ---------------------------------------------------------------------------
# Colorful execution over the precomputed batches (single- and multi-RHS)
# ---------------------------------------------------------------------------

def colorful_apply(M: CSRC, x, color_slots: np.ndarray,
                   color_slot_ptr: np.ndarray):
    """y = A·x color by color, using the schedule's precomputed slot batches.

    ``x`` may be (n,) or (n, r): inside one color every write target is
    unique, so ``.at[].add`` is a permutation write for any RHS width.
    """
    two_d = x.ndim == 2
    row_idx = jnp.asarray(row_of_slot(M))

    def bc(v):                  # broadcast slot values over RHS columns
        return v[:, None] if two_d else v

    y = (M.ad[:, None] if two_d else M.ad) * x[:M.n]
    ptr = np.asarray(color_slot_ptr)
    for c in range(len(ptr) - 1):
        sl = jnp.asarray(color_slots[ptr[c]:ptr[c + 1]])
        if sl.shape[0] == 0:
            continue
        r = row_idx[sl]
        j = M.ja[sl]
        y = y.at[r].add(bc(M.al[sl]) * x[j])
        y = y.at[j].add(bc(M.au[sl]) * x[r])
    return y
