"""Autotuner: measure feasible ExecutionPlans per matrix, cache the argmin.

This is the paper's per-matrix strategy-selection problem (§4: which of
local-buffers/accumulation-variants vs. colorful wins depends on the
matrix) solved the way RACE (arXiv:1907.06487) and Bergmans et al.
(arXiv:2502.19284) do it: enumerate feasible candidates from matrix
statistics, *measure* them, and remember the winner.

Pieces:

  MatrixStats / stats_of     the statistics that gate candidates
                             (bandwidth, nnz/row deviation, working set,
                             numeric symmetry)
  fingerprint                stable string key of a matrix *class*
                             (n, m, k, bandwidth, nnz-histogram digest)
  enumerate_plans            feasible candidates from stats, one
                             enumerator per registered KernelPath
                             (core/paths.py) — a new kernel path joins
                             every tuning run by registering; the legacy
                             @register_candidate_source hook also works
  heuristic_plan             measurement-free default (mirrors the old
                             static auto path, plus distributed strategy
                             selection from the collective-bytes model)
  PlanCache                  JSON plan cache keyed by fingerprint; a hit
                             skips re-measurement entirely
  tune / plan_for            the tuning entry points used by solvers
                             and the serve engine
  enumerate_mesh_plans /     the mesh-aware mode: distributed candidates
  tune_mesh / mesh_plan_for  (strategy='mesh', every accumulation x
                             shard-compute path, gated by the
                             collective-bytes model) measured on an
                             actual mesh of ``p`` forced host (or real)
                             devices; winners land in the cache under the
                             per-(matrix, p) key ``<fingerprint>@p<p>``

Mesh-aware tuning needs the process to see ``p`` devices — launch with
``XLA_FLAGS=--xla_force_host_platform_device_count=<p>`` on CPU (device
count is locked at first jax init, so tests run it in a subprocess).

Windowed candidates with ``value_dtype='bfloat16'`` (enumerated only for
numerically-symmetric matrices) additionally pass an accuracy check
against the exact segment-sum product before they may win
(``VALUE_DTYPE_TOL``): the tuner trades precision for value-stream
bandwidth only where the matrix class tolerates it.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro import obs
from . import paths as paths_mod
from .csrc import (CSRC, bandwidth as csrc_bandwidth, memo_digest,
                   nnz_per_row)
from .plan import ExecutionPlan, feasible, kernel_window

def _time_fn(fn, *args, warmup: int = 3, repeats: int = 10) -> float:
    """Median wall-clock seconds per call: ``warmup`` calls, then
    ``repeats`` calls each fenced with ``block_until_ready``."""
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


# ---------------------------------------------------------------------------
# Matrix statistics and fingerprinting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MatrixStats:
    n: int
    m: int
    k: int
    nnz: int
    bandwidth: int
    working_set_bytes: int
    nnz_row_mean: float
    nnz_row_dev: float            # std of nnz per row (load-balance driver)
    numerically_symmetric: bool
    lower_row_max: int            # most lower slots of any row (ell width)


def stats_of(M: CSRC) -> MatrixStats:
    w = nnz_per_row(M)
    return MatrixStats(
        n=M.n, m=M.m, k=M.k, nnz=M.nnz,
        bandwidth=csrc_bandwidth(M),
        working_set_bytes=M.working_set_bytes(),
        nnz_row_mean=float(w.mean()),
        nnz_row_dev=float(w.std()),
        numerically_symmetric=bool(M.numerically_symmetric),
        lower_row_max=int(np.diff(np.asarray(M.ia)).max(initial=0)),
    )


def fingerprint(M: CSRC) -> str:
    """Stable key of the matrix *class*: (n, m, k, bandwidth) in the clear
    plus a digest of the nnz-per-row histogram and symmetry flag.  Two
    matrices of the same class (same generator, same size) share a key, so
    solvers and the serve engine never re-tune a known class.  Computed
    once per device-backed instance (``csrc.memo_digest``)."""
    return memo_digest(M, "fingerprint", _fingerprint)


def _fingerprint(M: CSRC) -> str:
    with obs.span("tune.fingerprint"):
        w = nnz_per_row(M, site="fingerprint")
        hist = np.bincount(np.minimum(w, 255).astype(np.int64),
                           minlength=256)
        h = hashlib.sha1()
        h.update(hist.astype(np.int64).tobytes())
        h.update(bytes([int(M.numerically_symmetric)]))
        band = csrc_bandwidth(M, site="fingerprint")
    return f"n{M.n}m{M.m}k{M.k}b{band}-{h.hexdigest()[:12]}"


def mesh_fingerprint(fp: str, p: int) -> str:
    """Cache key of the per-(matrix class, mesh width) distributed tuning
    decision — the mesh-aware mode records one winner per (matrix, p)."""
    return f"{fp}@p{p}"


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------

_CANDIDATE_SOURCES: List[Callable[[MatrixStats], List[ExecutionPlan]]] = []


def register_candidate_source(fn):
    """Extension hook: future kernels register a function
    ``stats -> [ExecutionPlan, ...]``; its (feasible) plans join every
    enumeration and therefore every tuning run."""
    _CANDIDATE_SOURCES.append(fn)
    return fn


def _distributed_fields(stats: MatrixStats, p_hint: int = 8):
    """Analytic choice of the sharding degrees of freedom (not measured on
    a single chip): nnz-guided partition unless rows are uniform; halo when
    the band fits inside a shard (the collective-bytes model's winner),
    reduce_scatter otherwise."""
    partition = "nnz" if stats.nnz_row_dev > 0 else "count"
    rows_per_shard = max(1, -(-stats.n // p_hint))
    acc = ("halo" if stats.bandwidth <= max(8, rows_per_shard)
           else "reduce_scatter")
    return partition, acc


def enumerate_plans(stats: MatrixStats,
                    tms=(32, 128),
                    k_steps_sublanes=(8,),
                    w_cap: int = 4096,
                    colorful_max_n: int = 2048,
                    p_hint: int = 8,
                    nrhs_options=(1,),
                    index_dtypes=("int32", "int16"),
                    colorings=("greedy", "race")) -> List[ExecutionPlan]:
    """All feasible candidate plans for a matrix with these statistics.

    Candidates come from the KernelPath registry (core/paths.py): every
    registered path contributes its own enumerator over the sweep space —
    segment is always a candidate; windowed kernel plans ('kernel', and
    'flat' when the nnz-per-row skew makes per-tile-exact packing worth
    measuring) are emitted per (tm, k_step) whose window fits under
    ``w_cap``; colorful for square matrices small enough that the
    O(n·deg²) greedy coloring is worth attempting.  Legacy
    ``@register_candidate_source`` hooks still join the pool.

    Every candidate — registry or hook — is filtered through the path's
    feasibility predicate, so a plan the packer cannot tile (window over
    ``w_cap``, square-only path on a rectangular matrix) is rejected here
    instead of erroring mid-tune.

    ``nrhs_options`` replicates every candidate per RHS block width, so a
    serving deployment can tune the batched SpMM operating point directly
    (the winning path may differ between nrhs=1 and nrhs=8: arithmetic
    intensity rises with the block).

    ``index_dtypes`` controls the windowed paths' index-stream proposals:
    with the default both int32 and (where the window fits in 16 bits)
    int16 variants are measured, so the tuner trades index bandwidth per
    matrix — SpMV is bandwidth-bound, and int16 halves 8 of ~16 streamed
    bytes per slot.

    ``colorings`` controls the colorful enumerator's provider proposals:
    with the default both the greedy first-fit and the RACE recursive
    level-group coloring (arXiv:1907.06487) are candidates wherever the
    colored path is feasible, priced apart by the cost model's locality
    terms (per-color launch overhead x palette size + reuse-distance
    penalty) and measured per matrix.
    """
    partition, acc = _distributed_fields(stats, p_hint)
    space = paths_mod.CandidateSpace(
        tms=tuple(tms), k_steps_sublanes=tuple(k_steps_sublanes),
        w_cap=w_cap, colorful_max_n=colorful_max_n,
        partition=partition, accumulation=acc,
        index_dtypes=tuple(index_dtypes),
        colorings=tuple(colorings))
    raw: List[ExecutionPlan] = []
    for entry in paths_mod.registered_paths():
        raw.extend(entry.candidates(stats, space))
    for source in _CANDIDATE_SOURCES:
        raw.extend(source(stats))
    plans = [p for p in raw
             if feasible(p, n=stats.n, m=stats.m, bandwidth=stats.bandwidth)]
    if tuple(nrhs_options) != (1,):
        plans = [dataclasses.replace(p, nrhs=r)
                 for p in plans for r in nrhs_options]
    # dedup on the full plan (frozen dataclass), preserving order — key()
    # elides execution-irrelevant fields and must not drop distinct plans
    seen, out = set(), []
    for p in plans:
        if p not in seen:
            seen.add(p)
            out.append(p)
    return out


def heuristic_plan(stats: MatrixStats, tm: int = 128,
                   w_cap: int = 4096) -> ExecutionPlan:
    """Measurement-free plan: the old SpmvOperator 'auto' logic (kernel if
    the window fits, else segment) with the analytic distributed fields."""
    partition, acc = _distributed_fields(stats)
    square = stats.n == stats.m
    if square and kernel_window(tm, stats.bandwidth) <= w_cap:
        return ExecutionPlan(path="kernel", tm=tm, w_cap=w_cap,
                             partition=partition, accumulation=acc)
    return ExecutionPlan(path="segment", w_cap=w_cap,
                         partition=partition, accumulation=acc)


# ---------------------------------------------------------------------------
# Mesh-aware candidate enumeration (strategy='mesh' plans per shard count)
# ---------------------------------------------------------------------------

# A distributed candidate is dropped when its estimated collective traffic
# exceeds this multiple of the shard's compute-stream bytes (working set /
# p): past that point the product is collective-bound by construction and
# measuring it wastes tuning budget (Schubert et al., arXiv:0910.4836 —
# the strategy decision is a bandwidth/topology question).
MESH_COLLECTIVE_RATIO = 4.0


def _halo_fits(stats: MatrixStats, p: int) -> bool:
    ns = -(-stats.n // p)
    ns = (ns + 7) // 8 * 8
    h = max(8, (stats.bandwidth + 7) // 8 * 8)
    return h <= ns


def enumerate_mesh_plans(stats: MatrixStats, p: int,
                         tms=(32, 128),
                         k_steps_sublanes=(8,),
                         w_cap: int = 4096,
                         nrhs_options=(1,),
                         index_dtypes=("int32", "int16"),
                         max_collective_ratio: float = MESH_COLLECTIVE_RATIO
                         ) -> List[ExecutionPlan]:
    """Distributed candidate plans for a p-way mesh.

    Shard-local compute comes from the paths the distributed strategies
    execute — 'segment' always, and every path with a ShardSupport where
    its own enumerator proposes it ('ell' under the padding gate, 'flat'
    under the skew gate, as the local tuner) — crossed with every
    accumulation strategy whose collective footprint passes the
    bandwidth gate: 'halo' only when the band fits inside one shard, and
    any strategy only when ``collective_bytes_estimate`` stays within
    ``max_collective_ratio`` x the shard's working-set bytes.
    """
    from .distributed import collective_bytes_from_stats

    if stats.n != stats.m or p < 1:
        return []                 # distributed strategies shard square rows
    partition = "nnz" if stats.nnz_row_dev > 0 else "count"
    space = paths_mod.CandidateSpace(
        tms=tuple(tms), k_steps_sublanes=tuple(k_steps_sublanes),
        w_cap=w_cap, partition=partition,
        index_dtypes=tuple(index_dtypes),
        # the precision trade is not enumerated on the mesh yet (explicit
        # bf16 mesh plans execute; measuring them needs the accuracy gate
        # wired into the distributed measurement loop first)
        value_dtypes=("float32",))
    bases: List[ExecutionPlan] = []
    # shard-compute candidates: segment (the universal shard-local
    # fallback) plus every registered path with ShardSupport — the
    # distributed strategies can run those per shard
    for entry in paths_mod.registered_paths():
        if entry.name != "segment" and entry.shard_support is None:
            continue
        for cand in entry.candidates(stats, space):
            if feasible(cand, n=stats.n, m=stats.m,
                        bandwidth=stats.bandwidth):
                bases.append(cand)
    shard_ws = max(1, stats.working_set_bytes // p)
    out: List[ExecutionPlan] = []
    for acc in ("halo", "reduce_scatter", "allreduce"):
        if acc == "halo" and not _halo_fits(stats, p):
            continue
        for r in nrhs_options:
            est = collective_bytes_from_stats(
                stats.n, stats.bandwidth, p, acc, nrhs=r)
            if est > max_collective_ratio * shard_ws:
                continue          # collective-bound by construction
            for base in bases:
                out.append(dataclasses.replace(
                    base, strategy="mesh", mesh_p=p, accumulation=acc,
                    nrhs=r))
    return out


def heuristic_mesh_plan(stats: MatrixStats, p: int,
                        w_cap: int = 4096) -> ExecutionPlan:
    """Measurement-free distributed plan: segment shard compute with the
    collective-bytes model's strategy pick (the analytic fallback when the
    process cannot see p devices to measure on).  Raises ValueError for
    rectangular matrices — same gate as ``enumerate_mesh_plans`` (the
    distributed strategies shard square rows only)."""
    if stats.n != stats.m:
        raise ValueError(
            "distributed strategies shard square matrices only; serve "
            "rectangular matrices through a local plan")
    partition = "nnz" if stats.nnz_row_dev > 0 else "count"
    acc = "halo" if _halo_fits(stats, p) else "reduce_scatter"
    return ExecutionPlan(path="segment", w_cap=w_cap, partition=partition,
                         accumulation=acc, strategy="mesh", mesh_p=p)


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------

class PlanCache:
    """JSON plan cache keyed by matrix fingerprint.

    File format (version 1):

        {"version": 1,
         "entries": {"<fingerprint>": {"plan": {...ExecutionPlan fields...},
                                       "best_us": 12.3,
                                       "timings_us": {"<plan key>": 12.3},
                                       "pool_paths": ["ell", "segment"]}}}

    A ``get`` hit returns the stored plan without any re-measurement; the
    hit/miss counters let tests (and ops dashboards) assert that.  Entries
    carry a ``measured`` flag: heuristic (unmeasured) plans cached by
    ``plan_for(autotune=False)`` are visible to heuristic lookups but do
    NOT satisfy ``tune()``, which would otherwise report a never-measured
    plan as the argmin.  Nor does a measured entry whose ``pool_paths``
    lacks a path the current pool offers (or an entry older than the
    record): it never measured that path, so ``tune()`` (or, for a
    per-(matrix, p) entry, ``tune_mesh()``) measures again.

    Next to each plan the cache stores the **schedule artifact**
    (core/schedule.py): the block-ELL pack, row partition/halo ranges, and
    coloring the plan executes with.  Schedules live in memory plus — when
    the cache has a file path — as npz files under ``<stem>_schedules/``
    beside the JSON, keyed by (fingerprint, value digest, plan, partition
    width).  ``get_schedule`` hits mean zero pack/partition/coloring work;
    a schedule whose ``SCHEDULE_VERSION`` no longer matches is ignored and
    rebuilt (format-change invalidation).
    """

    VERSION = 1

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.entries: Dict[str, Dict] = {}
        self.hits = 0
        self.misses = 0
        self.schedules: Dict[str, object] = {}
        self.schedule_hits = 0
        self.schedule_misses = 0
        self.assembly_schedules: Dict[str, object] = {}
        self.assembly_hits = 0
        self.assembly_misses = 0
        self.shard_layouts: Dict[str, object] = {}
        self.shard_layout_hits = 0
        self.shard_layout_misses = 0
        # (fingerprint, plan key) -> (value digest, placed MeshExecutor),
        # kept by serve.executor.mesh_executor_for
        self.mesh_executors: Dict[tuple, tuple] = {}
        # (fingerprint, RHS widths) -> paths the enumerated pool offers
        self.offered_paths: Dict[tuple, frozenset] = {}
        if path is not None and os.path.exists(path):
            self._read(path)

    def _read(self, path: str):
        with open(path) as f:
            data = json.load(f)
        if data.get("version") != self.VERSION:
            raise ValueError(
                f"plan cache {path}: version {data.get('version')!r} "
                f"!= {self.VERSION}")
        self.entries = dict(data.get("entries", {}))

    def save(self, path: Optional[str] = None):
        path = path or self.path
        if path is None:
            raise ValueError("PlanCache.save: no path given or stored")
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": self.VERSION, "entries": self.entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        self.path = path

    def get(self, fp: str, require_measured: bool = False,
            offered: Optional[Callable[[], frozenset]] = None
            ) -> Optional[ExecutionPlan]:
        """The stored plan, or None.  ``offered`` gives the paths the
        caller's candidate pool offers now: an entry whose recorded pool
        lacks one of them (or records none) never measured that path, and
        counts as a miss."""
        e = self.entries.get(fp)
        if e is None or (require_measured and not e.get("measured")) or (
                offered is not None
                and not offered() <= set(e.get("pool_paths", ()))):
            self.misses += 1
            obs.counter("plan_cache_lookups_total", kind="plan",
                        outcome="miss").inc()
            return None
        self.hits += 1
        obs.counter("plan_cache_lookups_total", kind="plan",
                    outcome="hit").inc()
        env = e.get("env")
        if env:
            # a winner measured under a different toolchain/device is
            # identifiable; loading one bumps the warning counter per
            # disagreeing field (git SHA excluded — see obs.provenance)
            for field in obs.env_mismatches(env):
                obs.counter("plan_cache_env_mismatch_total",
                            field=field).inc()
        return ExecutionPlan.from_dict(e["plan"])

    def put(self, fp: str, plan: ExecutionPlan,
            timings_s: Optional[Dict[str, float]] = None,
            predictions_s: Optional[Dict[str, float]] = None,
            roofline: Optional[Dict[str, float]] = None,
            pool_paths: Optional[frozenset] = None):
        """``pool_paths`` records the paths of the candidate pool a
        measured plan won against (``get(offered=...)`` reads it).
        ``predictions_s`` (plan key -> analytic seconds) and
        ``roofline`` ({'predicted_ms', 'measured_ms', 'roofline_fraction'}
        of the winner) are the predict-then-measure provenance: the cache
        records what the cost model claimed next to what the clock said —
        and ``env`` records which jax/device/git environment measured it
        (obs.environment_provenance)."""
        entry: Dict = {"plan": plan.to_dict(),
                       "measured": bool(timings_s),
                       "env": dict(obs.environment_provenance())}
        if pool_paths is not None:
            entry["pool_paths"] = sorted(pool_paths)
        if timings_s:
            entry["timings_us"] = {k: round(v * 1e6, 3)
                                   for k, v in timings_s.items()}
            entry["best_us"] = round(min(timings_s.values()) * 1e6, 3)
        if predictions_s:
            entry["predicted_us"] = {k: round(v * 1e6, 3)
                                     for k, v in predictions_s.items()}
        if roofline:
            entry.update({k: roofline[k] for k in
                          ("predicted_ms", "measured_ms",
                           "roofline_fraction") if k in roofline})
        self.entries[fp] = entry

    # ---- schedule artifacts (stored next to the plans) ----

    def _schedule_dir(self) -> Optional[str]:
        if self.path is None:
            return None
        stem, _ = os.path.splitext(os.path.abspath(self.path))
        return stem + "_schedules"

    def get_schedule(self, fp: str, digest: str, plan: ExecutionPlan,
                     p: int = 8):
        """The cached schedule for (matrix, plan), or None.  Memory first,
        then the npz file beside the cache; version/plan mismatches count
        as misses (the caller rebuilds)."""
        from .schedule import (SpmvSchedule, plan_artifact_fields,
                               schedule_key)
        key = schedule_key(fp, digest, plan, p)
        sched = self.schedules.get(key)
        if sched is None:
            d = self._schedule_dir()
            f = None if d is None else os.path.join(d, key + ".npz")
            if f is not None and os.path.exists(f):
                try:
                    sched = SpmvSchedule.load_npz(f)
                except Exception:         # stale version, truncated or
                    sched = None          # foreign file: rebuild, not crash
                if sched is not None and (
                        plan_artifact_fields(sched.plan)
                        != plan_artifact_fields(plan)
                        or sched.value_digest != digest):
                    sched = None
                if sched is not None:
                    self.schedules[key] = sched
        if sched is None:
            self.schedule_misses += 1
            obs.counter("plan_cache_lookups_total", kind="schedule",
                        outcome="miss").inc()
            return None
        self.schedule_hits += 1
        obs.counter("plan_cache_lookups_total", kind="schedule",
                    outcome="hit").inc()
        return sched

    def put_schedule(self, sched, persist: bool = True):
        """Store a schedule (memory, and — for path-backed caches — as an
        npz beside the plans).  ``persist=False`` keeps it memory-only:
        the value-refresh path uses it so per-step time stepping does not
        re-compress a full npz (values + unchanged index streams) every
        step; the structural generation already on disk keeps serving
        fresh processes, which value-refresh from it on load."""
        key = sched.key()
        self.schedules[key] = sched
        d = self._schedule_dir()
        if persist and d is not None:
            sched.save_npz(os.path.join(d, key + ".npz"))

    def drop_schedule(self, sched, remove_file: bool = True):
        """Evict a schedule from memory (and, by default, its npz).  Used
        by the value-refresh path to replace a superseded value
        generation: time stepping keeps exactly one schedule per
        (structure, plan, p) in memory — the newest — so a 10k-step run
        does not accumulate 10k dead value streams."""
        key = sched.key()
        self.schedules.pop(key, None)
        d = self._schedule_dir()
        if remove_file and d is not None:
            try:
                os.remove(os.path.join(d, key + ".npz"))
            except OSError:
                pass

    def find_schedule_by_structure(self, fp: str, sdigest: str, plan,
                                   p: int = 8):
        """A cached schedule for the same matrix *structure* (fingerprint +
        structure digest + plan artifact geometry + partition width) whose
        values may differ — the FEM time-stepping fast path: the caller
        refreshes value streams (``schedule.refresh_schedule``) instead of
        re-packing/re-coloring.  In-memory schedules only: the scenario is
        repeated refreshes within one serving/solver process."""
        from .schedule import plan_artifact_fields
        fields = plan_artifact_fields(plan)
        for sched in self.schedules.values():
            if (sched.fingerprint == fp and sched.p == p
                    and sched.structure_digest == sdigest
                    and plan_artifact_fields(sched.plan) == fields):
                return sched
        return None

    # ---- distributed shard layouts (ShardedSlots / HaloLayout /
    # FlatShards / FlatHalo), stored beside the schedules and keyed by
    # (fingerprint, value digest, p, strategy kind, pack geometry) — the
    # npz layer that ships per-shard sub-artifacts to serving workers ----

    def get_shard_layout(self, key: str):
        """The cached distributed layout for this key, or None.  Memory
        first, then the npz beside the plans — a hit means zero per-shard
        pack/layout construction (the mesh executor's artifact-shipping
        path)."""
        from .schedule import load_shard_layout_npz
        lay = self.shard_layouts.get(key)
        if lay is None:
            d = self._schedule_dir()
            f = None if d is None else os.path.join(d, key + ".npz")
            if f is not None and os.path.exists(f):
                try:
                    lay = load_shard_layout_npz(f)
                except Exception:     # stale version / truncated: rebuild
                    lay = None
                if lay is not None:
                    self.shard_layouts[key] = lay
        if lay is None:
            self.shard_layout_misses += 1
            obs.counter("plan_cache_lookups_total", kind="shard_layout",
                        outcome="miss").inc()
            return None
        self.shard_layout_hits += 1
        obs.counter("plan_cache_lookups_total", kind="shard_layout",
                    outcome="hit").inc()
        return lay

    def put_shard_layout(self, key: str, lay, persist: bool = True):
        from .schedule import save_shard_layout_npz
        self.shard_layouts[key] = lay
        d = self._schedule_dir()
        if persist and d is not None:
            save_shard_layout_npz(os.path.join(d, key + ".npz"), lay)

    # ---- assembly schedules (repro.assembly.scatter), stored beside the
    # SpMV schedules and keyed by connectivity digest ----

    def get_assembly_schedule(self, digest: str, num_buffers: int = 8,
                              coloring: str = "greedy"):
        """The cached AssemblySchedule for this connectivity digest, or
        None.  Memory first, then the npz beside the cache — a hit means
        zero structural assembly work (slot maps, coloring, buffers).
        ``coloring`` picks the element-coloring provider slice of the
        cache (greedy keys are unchanged from pre-provider caches)."""
        from repro.assembly.scatter import AssemblySchedule, assembly_key
        key = assembly_key(digest, num_buffers, coloring)
        sched = self.assembly_schedules.get(key)
        if sched is None:
            d = self._schedule_dir()
            f = None if d is None else os.path.join(d, key + ".npz")
            if f is not None and os.path.exists(f):
                try:
                    sched = AssemblySchedule.load_npz(f)
                except Exception:      # stale version / truncated: rebuild
                    sched = None
                if sched is not None and (
                        sched.structure_digest != digest
                        or sched.coloring.provider != coloring):
                    sched = None
                if sched is not None:
                    self.assembly_schedules[key] = sched
        if sched is None:
            self.assembly_misses += 1
            obs.counter("plan_cache_lookups_total", kind="assembly",
                        outcome="miss").inc()
            return None
        self.assembly_hits += 1
        obs.counter("plan_cache_lookups_total", kind="assembly",
                    outcome="hit").inc()
        return sched

    def put_assembly_schedule(self, sched):
        key = sched.key()
        self.assembly_schedules[key] = sched
        d = self._schedule_dir()
        if d is not None:
            sched.save_npz(os.path.join(d, key + ".npz"))

    # ---- assembly strategy plans (assembly.scatter.tune_assembly):
    # the tuned (strategy, variant) winner + predict/measure provenance,
    # stored as a JSON record under "asmplan-<schedule key>" ----

    def get_assembly_plan(self, key: str):
        """The tuned assembly record for this schedule key, or None."""
        e = self.entries.get(key)
        rec = None if e is None else e.get("assembly")
        if rec is None:
            obs.counter("plan_cache_lookups_total", kind="assembly_plan",
                        outcome="miss").inc()
            return None
        obs.counter("plan_cache_lookups_total", kind="assembly_plan",
                    outcome="hit").inc()
        return dict(rec)

    def put_assembly_plan(self, key: str, record: Dict):
        self.entries[key] = {"assembly": dict(record), "measured": True}
        if self.path:
            self.save()

    def __len__(self) -> int:
        return len(self.entries)


# ---------------------------------------------------------------------------
# Tuning
# ---------------------------------------------------------------------------

# Max relative error a reduced-precision (value_dtype != 'float32')
# candidate may show against the exact segment-sum product before the
# tuner rejects it — the accuracy gate of the bf16 value-stream trade.
VALUE_DTYPE_TOL = 2e-2


def _rhs_pool(M: CSRC, x: Optional[np.ndarray]):
    """Measurement inputs per RHS block width, shared by the local and
    mesh tuners: multi-RHS candidates are measured at their tuned width
    (seeded per width, memoized)."""
    import jax.numpy as jnp
    if x is None:
        x = np.random.default_rng(0).standard_normal(M.m).astype(np.float32)
    xj = jnp.asarray(x)
    by_width = {1: xj} if xj.ndim == 1 else {xj.shape[1]: xj, 1: xj[:, 0]}

    def x_for(nrhs: int):
        if nrhs not in by_width:
            by_width[nrhs] = jnp.asarray(
                np.random.default_rng(nrhs).standard_normal(
                    (M.m, nrhs)).astype(np.float32))
        return by_width[nrhs]

    return x_for


@dataclasses.dataclass(frozen=True)
class TuneResult:
    plan: ExecutionPlan
    fingerprint: str
    timings_s: Dict[str, float]   # plan.key() -> seconds; empty on cache hit
    cached: bool
    # per-p distributed winners when tune() ran with mesh_ps (empty
    # otherwise); also recorded in the cache under mesh_fingerprint keys
    mesh_plans: Dict[int, ExecutionPlan] = dataclasses.field(
        default_factory=dict)
    # predict-then-measure provenance: plan.key() -> analytic roofline
    # seconds for every ranked candidate (superset of timings_s keys when
    # pruning ran), and the winner's achieved-roofline fraction
    predictions_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    roofline_fraction: Optional[float] = None


def _offered_paths(M: CSRC, fp: str, cache: PlanCache, candidates,
                   nrhs_options, p: Optional[int] = None) -> frozenset:
    """The paths the candidate pool of this tuning request offers (the
    ``p``-way mesh pool when ``p`` is given).  The enumerated pool's are
    kept per (fingerprint, RHS widths) in the cache: computing the
    statistics on every hit would put host work on each call's path."""
    if candidates is not None:
        return frozenset(c.path for c in candidates)
    key = (fp, tuple(nrhs_options))
    if key not in cache.offered_paths:
        stats = stats_of(M)
        pool = (enumerate_plans(stats, nrhs_options=key[1]) if p is None
                else enumerate_mesh_plans(stats, p, nrhs_options=key[1]))
        cache.offered_paths[key] = frozenset(c.path for c in pool)
    return cache.offered_paths[key]


def tune(M: CSRC,
         cache: Optional[PlanCache] = None,
         x: Optional[np.ndarray] = None,
         candidates: Optional[List[ExecutionPlan]] = None,
         measure: Optional[Callable] = None,
         warmup: int = 1,
         repeats: int = 3,
         interpret=None,
         save: bool = True,
         value_dtype_tol: float = VALUE_DTYPE_TOL,
         predict: bool = True,
         measure_top_k: Optional[int] = None,
         nrhs_options=(1,),
         mesh_ps=()) -> TuneResult:
    """Rank candidates by the analytic roofline, measure the top few, and
    return the argmin plan.

    ``cache`` short-circuits: a fingerprint hit returns the stored plan
    with zero measurements.  ``measure(op, x) -> seconds`` is injectable
    for tests; the default is ``_time_fn``, the median of fenced calls, with a
    small budget (the tuner runs at operator-construction time).

    ``predict=True`` (default) is the predict-then-measure mode: every
    feasible candidate is priced by roofline/cost_model.py (bytes/flops
    from matrix statistics — no packing, no timing) and only the
    ``measure_top_k`` cheapest-predicted plans are clocked (default
    max(3, quarter of the pool) — a >= 2x measurement cut on every suite
    matrix), plus each distinct path's best-predicted candidate so a
    cross-path mispricing can never exclude a path from measurement.  The cache entry records ``predicted_us`` per ranked
    candidate plus the winner's ``predicted_ms`` / ``measured_ms`` /
    ``roofline_fraction`` (least time the measuring device's peaks allow
    over the measured time; ``None`` on a device kind without a peak
    row).  ``predict=False`` measures the full pool
    (the oracle mode the pruned tuner is validated against in tests).

    Candidates with a reduced ``value_dtype`` must additionally match the
    exact segment-sum product within ``value_dtype_tol`` relative error or
    they are rejected before measurement (the bf16 accuracy gate).

    ``nrhs_options`` is the serving-time batched operating point: every
    candidate is replicated per RHS block width and measured at that
    width (argmin on per-column time), so a serving deployment that
    coalesces requests into multi-RHS blocks tunes the block product it
    will actually run — the winner's ``plan.nrhs`` records the width.

    ``mesh_ps`` is the mesh-aware mode: for every shard count listed the
    distributed candidates are measured on an actual ``p``-device mesh
    (``tune_mesh``) and the per-(matrix, p) winner is recorded in the
    cache under ``mesh_fingerprint(fp, p)`` — the process must see that
    many devices (forced host platform on CPU).
    """
    from repro.kernels.ops import SpmvOperator   # local: avoid import cycle

    fp = fingerprint(M)

    def offered():
        return _offered_paths(M, fp, cache, candidates, nrhs_options)

    if cache is not None and not mesh_ps:
        # a heuristic (unmeasured) entry must not satisfy a tune request,
        # nor one measured before a path of the current pool existed
        hit = cache.get(fp, require_measured=True, offered=offered)
        if hit is not None:
            return TuneResult(plan=hit, fingerprint=fp, timings_s={},
                              cached=True)

    stats = stats_of(M)
    cands = (candidates if candidates is not None
             else enumerate_plans(stats, nrhs_options=tuple(nrhs_options)))
    if measure is None:
        def measure(op, xv):
            return _time_fn(op, xv, warmup=warmup, repeats=repeats)
    _x_for = _rhs_pool(M, x)

    _y_ref_by_width: Dict[int, np.ndarray] = {}

    def _accuracy_ok(op, nrhs: int) -> bool:
        """Reduced-precision gate: compare against the exact product."""
        from repro.kernels import ref as ref_mod
        xv = _x_for(nrhs)
        if nrhs not in _y_ref_by_width:
            y_ref = (ref_mod.csrc_spmm(M, xv) if xv.ndim == 2
                     else ref_mod.csrc_spmv(M, xv))
            _y_ref_by_width[nrhs] = np.asarray(y_ref, dtype=np.float64)
        y_ref = _y_ref_by_width[nrhs]
        y = np.asarray(op(xv), dtype=np.float64)
        scale = max(1.0, float(np.abs(y_ref).max()))
        return float(np.abs(y - y_ref).max()) / scale <= value_dtype_tol

    cached_local = False
    if cache is not None and mesh_ps:
        hit = cache.get(fp, require_measured=True, offered=offered)
    else:
        hit = None

    timings: Dict[str, float] = {}
    predictions: Dict[str, float] = {}
    winner_frac: Optional[float] = None
    if hit is not None:
        best_plan, cached_local = hit, True
    else:
        pool = [p for p in cands
                if feasible(p, n=M.n, m=M.m, bandwidth=stats.bandwidth)]
        obs.counter("tuner_candidates_enumerated_total").inc(len(pool))
        est_by_key: Dict[str, object] = {}
        if predict and pool:
            from repro.roofline import cost_model
            ranked = cost_model.rank_plans(stats, pool)
            est_by_key = {p.key(): e for p, e in ranked}
            predictions = {p.key(): e.predicted_s for p, e in ranked}
            k_top = (measure_top_k if measure_top_k
                     else max(3, len(ranked) // 4))
            pool = [p for p, _ in ranked[:max(2, k_top)]]
            # path-diversity guarantee: the analytic model ranks *within*
            # a path reliably but can misprice one path against another
            # (padding on skewed row distributions is the known case), so
            # every distinct path keeps its best-predicted candidate in
            # the measured set — at most one extra measurement per path,
            # which preserves the >= 2x cut on pools of 10+ plans
            seen_paths = {p.path for p in pool}
            for p, _ in ranked:
                if p.path not in seen_paths:
                    seen_paths.add(p.path)
                    pool.append(p)
            pruned = len(ranked) - len(pool)
            obs.counter("tuner_candidates_pruned_total").inc(pruned)
        best_plan, best_t, best_raw, best_op = None, float("inf"), None, None
        for p in pool:
            with obs.span("tune.measure", plan=p.key()):
                try:
                    op = SpmvOperator.from_plan(M, p, interpret=interpret)
                except ValueError:
                    continue      # pack-time infeasibility (bandwidth gate)
                if (p.value_dtype != "float32"
                        and not _accuracy_ok(op, p.nrhs)):
                    continue      # precision trade failed the gate
                t = float(measure(op, _x_for(p.nrhs)))
            obs.counter("tuner_candidates_measured_total").inc()
            timings[p.key()] = t
            # argmin on per-RHS-column time: an nrhs=8 candidate does 8x
            # the work of a single product, so raw runtimes are not
            # comparable across block widths
            t_norm = t / p.nrhs
            if t_norm < best_t:
                best_plan, best_t, best_raw, best_op = p, t_norm, t, op
        if best_plan is None:
            raise ValueError("no feasible execution plan for this matrix")

        roofline_entry: Optional[Dict[str, float]] = None
        est = est_by_key.get(best_plan.key())
        if est is not None and best_raw:
            import jax
            from repro.roofline import cost_model
            # None off the peak table: no share is made up for the CPU
            winner_frac = cost_model.roofline_fraction(
                est, best_raw, jax.devices()[0].device_kind)
            roofline_entry = {
                "predicted_ms": round(est.predicted_s * 1e3, 6),
                "measured_ms": round(best_raw * 1e3, 6),
                "roofline_fraction": winner_frac,
            }
        if cache is not None:
            cache.put(fp, best_plan, timings, predictions_s=predictions,
                      roofline=roofline_entry,
                      pool_paths=frozenset(p.path for p in cands))
            # store the winner's schedule next to the plan: serving
            # processes constructing this (matrix, plan) never re-pack or
            # re-color
            if (best_op is not None
                    and getattr(best_op, "schedule", None) is not None):
                cache.put_schedule(best_op.schedule)
            if save and cache.path is not None:
                cache.save()

    mesh_plans: Dict[int, ExecutionPlan] = {}
    for p_mesh in mesh_ps:
        res = tune_mesh(M, p_mesh, cache=cache, x=x, measure=measure,
                        warmup=warmup, repeats=repeats,
                        interpret=interpret, save=save,
                        nrhs_options=nrhs_options)
        mesh_plans[p_mesh] = res.plan
    return TuneResult(plan=best_plan, fingerprint=fp, timings_s=timings,
                      cached=cached_local, mesh_plans=mesh_plans,
                      predictions_s=predictions,
                      roofline_fraction=winner_frac)


def tune_mesh(M: CSRC, p: int,
              cache: Optional[PlanCache] = None,
              mesh=None,
              axis: str = "rows",
              x: Optional[np.ndarray] = None,
              candidates: Optional[List[ExecutionPlan]] = None,
              measure: Optional[Callable] = None,
              warmup: int = 1,
              repeats: int = 3,
              interpret=None,
              save: bool = True,
              nrhs_options=(1,)) -> TuneResult:
    """The mesh-aware tuning mode: measure distributed candidates on an
    actual p-device mesh and cache the per-(matrix, p) winner.

    ``nrhs_options`` replicates the distributed candidates per RHS block
    width exactly as in :func:`tune` — the serving engine passes its
    batched operating point so the per-(matrix, p) winner is tuned for
    the block product it serves, not for nrhs=1.

    The winner is recorded under ``mesh_fingerprint(fingerprint(M), p)``,
    so local and distributed decisions for one matrix class coexist in
    the same cache: the serving engine asks for the mesh entry when it
    has a mesh to serve from, and the local entry otherwise.  The process
    must see ``p`` devices (``XLA_FLAGS=--xla_force_host_platform_
    device_count=<p>`` on CPU); a ``measure(fn, x) -> seconds`` injection
    makes the mode testable on one device with a 1-wide mesh.  Like
    ``tune``, the entry records its pool's paths: a cached winner whose
    pool lacked a path the mesh pool offers now is measured again.
    """
    import jax
    from .distributed import build_sharded_spmv, make_mesh

    fp = mesh_fingerprint(fingerprint(M), p)
    if cache is not None:
        # as in tune(): an entry measured over a pool without a path the
        # mesh pool offers now (or recording none) is measured again
        hit = cache.get(fp, require_measured=True,
                        offered=lambda: _offered_paths(
                            M, fp, cache, candidates, nrhs_options, p=p))
        if hit is not None:
            return TuneResult(plan=hit, fingerprint=fp, timings_s={},
                              cached=True)

    if mesh is None:
        ndev = len(jax.devices())
        if ndev < p:
            raise ValueError(
                f"mesh-aware tuning for p={p} needs {p} devices, this "
                f"process sees {ndev}; relaunch with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={p}")
        mesh = make_mesh(p, axis)

    stats = stats_of(M)
    cands = (candidates if candidates is not None
             else enumerate_mesh_plans(stats, p,
                                       nrhs_options=tuple(nrhs_options)))
    if not cands:
        raise ValueError(
            f"no feasible distributed plan for this matrix at p={p}")
    if measure is None:
        def measure(fn, xv):
            return _time_fn(fn, xv, warmup=warmup, repeats=repeats)
    _x_for = _rhs_pool(M, x)

    timings: Dict[str, float] = {}
    best_plan, best_t = None, float("inf")
    for cand in cands:
        try:
            # measured WITHOUT the cache: only the argmin's artifacts
            # are shipped (below) — losers would otherwise persist one
            # matrix-sized npz per candidate geometry
            fn = build_sharded_spmv(M, mesh, axis, strategy="auto",
                                    cache=None, plan=cand,
                                    interpret=interpret)
        except ValueError:
            continue              # halo band gate / window over cap
        with obs.span("tune.measure_mesh", plan=cand.key(), p=p):
            t = float(measure(fn, _x_for(cand.nrhs)))
        obs.counter("tuner_candidates_measured_total").inc()
        timings[cand.key()] = t
        t_norm = t / cand.nrhs
        if t_norm < best_t:
            best_plan, best_t = cand, t_norm
    if best_plan is None:
        raise ValueError(
            f"no distributed candidate survived measurement at p={p}")

    if cache is not None:
        pool_paths = frozenset(c.path for c in cands)
        cache.put(fp, best_plan, timings, pool_paths=pool_paths)
        if candidates is None:
            # the pool just enumerated is what the next probe's offer
            # check would compute from the statistics again
            cache.offered_paths[(fp, tuple(nrhs_options))] = pool_paths
        # ship the winner's schedule + shard-layout artifacts (layout
        # builders re-serve the memoized build and persist it)
        build_sharded_spmv(M, mesh, axis, strategy="auto", cache=cache,
                           plan=best_plan, interpret=interpret)
        if save and cache.path is not None:
            cache.save()
    return TuneResult(plan=best_plan, fingerprint=fp, timings_s=timings,
                      cached=False)


def plan_for(M: CSRC,
             cache: Optional[PlanCache] = None,
             autotune: bool = False,
             **tune_kw) -> ExecutionPlan:
    """The plan to run this matrix with.

    Cache hit wins; otherwise ``autotune=True`` measures (and fills the
    cache), ``autotune=False`` falls back to the measurement-free
    heuristic (still cached, so the decision is stable across calls).
    """
    with obs.span("tune.resolve"):
        if autotune:
            # tune() performs the cache probe itself — probing here too
            # would double-count misses and fingerprint twice
            return tune(M, cache=cache, **tune_kw).plan
        fp = fingerprint(M)
        if cache is not None:
            hit = cache.get(fp)
            if hit is not None:
                return hit
        plan = heuristic_plan(stats_of(M))
        if cache is not None:
            cache.put(fp, plan)
            if cache.path is not None:
                cache.save()
        return plan


def mesh_plan_for(M: CSRC, p: int,
                  cache: Optional[PlanCache] = None,
                  autotune: bool = False,
                  interpret=None,
                  **tune_kw) -> ExecutionPlan:
    """The distributed plan to serve this matrix with on a p-way mesh.

    Mirrors :func:`plan_for` for the per-(matrix, p) cache keys: hit wins;
    ``autotune=True`` measures on an actual mesh (``tune_mesh``);
    ``autotune=False`` falls back to the collective-bytes heuristic
    (cached, so the decision is stable across calls)."""
    with obs.span("tune.resolve"):
        if autotune:
            return tune_mesh(M, p, cache=cache, interpret=interpret,
                             **tune_kw).plan
        fp = mesh_fingerprint(fingerprint(M), p)
        if cache is not None:
            hit = cache.get(fp)
            if hit is not None:
                return hit
        plan = heuristic_mesh_plan(stats_of(M), p)
        if cache is not None:
            cache.put(fp, plan)
            if cache.path is not None:
                cache.save()
        return plan
