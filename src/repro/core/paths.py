"""The KernelPath registry: one registration per execution path.

Before this layer, adding a kernel path meant editing five places in
lock-step: the ``if path == ...`` chain in ``kernels/ops.py``, the
validation tuple and feasibility function in ``core/plan.py``, the
candidate enumeration in ``core/tuner.py``, and the artifact
build/serialize branches in ``core/schedule.py``.  The registry collapses
those into one record per path (docs/DESIGN.md §3):

  name              the ``ExecutionPlan.path`` value
  feasible          can this path execute a matrix with these shape stats
                    at all (the tuner filters candidates through this —
                    an infeasible plan is rejected up front, never
                    mid-tune)
  candidates        tuner candidate enumerator: the plans worth measuring
                    for a matrix with the given statistics
  artifact_fields   the plan fields the schedule artifact depends on
                    (plans differing only elsewhere share one artifact)
  build_artifact    packer / coloring builder -> SpmvSchedule field dict
  save_artifact     npz serialization of those fields (meta, arrays)
  load_artifact     the inverse; versioned via schedule.SCHEDULE_VERSION
  make_spmv         executor factory, x of shape (m,)
  make_spmm         executor factory, X of shape (m, r)
  refresh_values    same-structure value-stream refresh (FEM time
                    stepping; schedule.refresh_schedule) — optional

``register_path`` wires the name into ``plan.PATHS`` (so ``ExecutionPlan``
validation accepts it) and makes the path visible to the operator, the
schedule layer, the tuner, and — through schedule's shard-layout builders —
the distributed strategies.  Adding a path is one registration, not five
edits; the built-in registrations below double as the template.

Executors live in ``repro.kernels`` — imported lazily inside the factory
functions so the core package keeps its import order (kernels imports
core, never the reverse at module load).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

from .plan import ExecutionPlan, kernel_window, register_path_name

# Build probe: how many times each expensive structure precomputation ran.
# Tests (and ops dashboards) diff these counters around a cache-hit path to
# assert that no re-pack / re-partition / re-coloring happened.  (Re-exported
# as ``schedule.BUILD_COUNTS`` — same object.)
#
# Since the obs spine landed this is a thin dict-like compat shim over the
# real ``build_total{kind=...}`` counter family in ``repro.obs.REGISTRY``:
# reads (``BUILD_COUNTS['pack']``, ``dict(BUILD_COUNTS)``, ``.items()``)
# behave exactly like the old collections.Counter, and the build sites call
# ``BUILD_COUNTS.inc(kind)``.  Direct item assignment (the old
# ``BUILD_COUNTS[k] += 1`` pattern) still works but is deprecated — it warns and will be removed once
# external probes migrate to ``obs.counter('build_total', kind=...)``.
class BuildCounts:
    """Counter-compatible view over the ``build_total`` metric family."""

    FAMILY = "build_total"
    _HELP = ("expensive structure precomputations (pack / partition / "
             "coloring / shard layouts) that actually ran")

    def _family(self):
        from repro import obs
        return obs.REGISTRY.family(self.FAMILY, "counter", ("kind",),
                                   help=self._HELP)

    def inc(self, kind: str, v: int = 1):
        """Record ``v`` builds of this kind.  Counts even when metrics
        are disabled: the probe is a correctness assertion, not
        telemetry."""
        self._family().labels(kind=kind).inc_always(v)

    def __getitem__(self, kind: str) -> int:
        child = self._family().children.get((str(kind),))
        return 0 if child is None else int(child.value)

    def __setitem__(self, kind: str, v):
        import warnings
        warnings.warn(
            "direct BUILD_COUNTS mutation is deprecated; use "
            "BUILD_COUNTS.inc(kind) or obs.counter('build_total', ...)",
            DeprecationWarning, stacklevel=2)
        self._family().labels(kind=kind).set_always(v)

    def get(self, kind: str, default: int = 0) -> int:
        v = self[kind]
        return v if (str(kind),) in self._family().children else default

    def keys(self):
        return [k for (k,) in self._family().children]

    def values(self):
        return [int(c.value) for c in self._family().children.values()]

    def items(self):
        return [(k, int(c.value))
                for (k,), c in self._family().children.items()]

    def __iter__(self):
        return iter(self.keys())

    def __len__(self) -> int:
        return len(self._family().children)

    def __contains__(self, kind) -> bool:
        return (str(kind),) in self._family().children

    def __repr__(self) -> str:
        return f"BuildCounts({dict(self.items())!r})"


BUILD_COUNTS = BuildCounts()


@dataclasses.dataclass(frozen=True)
class CandidateSpace:
    """The degrees of freedom ``tuner.enumerate_plans`` sweeps, plus the
    analytically-chosen distributed fields every candidate inherits."""
    tms: Tuple[int, ...] = (32, 128)
    k_steps_sublanes: Tuple[int, ...] = (8,)
    w_cap: int = 4096
    colorful_max_n: int = 2048
    partition: str = "nnz"
    accumulation: str = "allreduce"
    # index-stream dtypes the windowed enumerators propose; 'int16' is
    # emitted only where the pack supports it (window fits in 16 bits),
    # letting the tuner trade index bandwidth per matrix
    index_dtypes: Tuple[str, ...] = ("int32", "int16")
    # value-stream dtypes the windowed enumerators propose; 'bfloat16' is
    # emitted only for numerically-symmetric matrices (the well-conditioned
    # suite classes) and must additionally pass the tuner's accuracy check
    # before it can win
    value_dtypes: Tuple[str, ...] = ("float32", "bfloat16")
    # chunk sizes (sublanes; S = ks*128 stream entries per chunk) the
    # nnz-split enumerator sweeps: small chunks bound the per-chunk row
    # window, large chunks amortize the per-program overhead
    nnzsplit_ks: Tuple[int, ...] = (2, 8)
    # body variants the windowed / nnz-split enumerators propose: 'stream'
    # (fused XLA gather + segment-sum, bandwidth-bound) and 'onehot' (the
    # Pallas kernel's MXU one-hot contractions).  Both share one pack
    # artifact — variant is not an artifact field — so proposing both
    # costs no extra schedule builds.
    variants: Tuple[str, ...] = ("stream", "onehot")
    # coloring providers the colorful enumerator proposes (core/coloring):
    # 'greedy' sequential first-fit and 'race' recursive level-groups.
    # The provider is an artifact field — greedy and race schedules cache
    # under distinct keys — and the cost model prices the locality gap
    # (launch count x reuse distance) so predict-then-measure separates
    # them before the first coloring is ever built.
    colorings: Tuple[str, ...] = ("greedy", "race")


@dataclasses.dataclass(frozen=True)
class ShardSupport:
    """How a path executes *shard-locally* inside the distributed
    strategies (core/distributed.py) and the serving ``MeshExecutor``.

    A path without one (``KernelPath.shard_support is None``) still works
    on a mesh — the strategies fall back to the segment-sum shard-local
    product — but a path that registers one is served end-to-end by all
    three accumulation strategies over its own per-shard sub-packs, with
    the schedule layer memoizing/shipping the layouts and
    ``refresh_shard_layout`` refreshing their value streams.  This is the
    registry's answer to the former ``if plan.path == 'flat'`` special
    cases in distributed.py / executor.py / tuner.py / schedule.py.

      shards_kind     npz-kind + BUILD_COUNTS key of the row-partition
                      layout (allreduce / reduce_scatter)
      halo_kind       likewise for the local-coordinate halo layout
      layout_classes  () -> {kind: dataclass} (lazy kernel import)
      geometry        plan -> the plan-derived geometry tuple layouts are
                      keyed by (memoization + npz cache keys)
      pack_shards     (M, starts, plan) -> shards layout
      pack_halo       (M, p, plan) -> halo layout (ValueError: band gate)
      refresh_shards  (layout, M, starts) -> value-refreshed layout
      refresh_halo    (layout, M) -> value-refreshed layout
      shard_arrays    layout -> tuple of leading-axis-p device arrays
                      (the strategies split each on that axis alone)
      local_fn        (layout, n_local, interpret, variant) -> local
                      product: the path's one-hot Pallas kernel or its
                      fused stream form
                      fn(*shard_arrays, x) -> y  (n_local rows)
      halo_dims       halo layout -> (ns, h, n_local)
    """
    shards_kind: str
    halo_kind: str
    layout_classes: Callable[[], dict]
    geometry: Callable[[ExecutionPlan], tuple]
    pack_shards: Callable[..., object]
    pack_halo: Callable[..., object]
    refresh_shards: Callable[..., object]
    refresh_halo: Callable[..., object]
    shard_arrays: Callable[[object], tuple]
    local_fn: Callable[..., Callable]
    halo_dims: Callable[[object], tuple]


@dataclasses.dataclass(frozen=True)
class KernelPath:
    """Everything the plan/schedule/tuner/operator stack needs to know
    about one execution path."""
    name: str
    feasible: Callable[..., bool]
    candidates: Callable[..., list]
    artifact_fields: Callable[[ExecutionPlan], tuple]
    build_artifact: Callable[..., dict]
    save_artifact: Callable[..., Tuple[dict, dict]]
    load_artifact: Callable[..., dict]
    make_spmv: Callable[..., Callable]
    make_spmm: Callable[..., Callable]
    # Same-structure value refresh (M, schedule) -> updated artifact field
    # dict (schedule.refresh_schedule).  None means the path's artifact is
    # purely structural (or absent) and is reused as-is — the executors
    # read values from the matrix directly ('segment', 'colorful').
    refresh_values: Optional[Callable[..., dict]] = None
    # Shard-local execution hooks for the distributed strategies and the
    # serving MeshExecutor.  None means the path runs shard-locally as
    # segment-sum (distributed.py's fallback).
    shard_support: Optional[ShardSupport] = None


_REGISTRY: Dict[str, KernelPath] = {}


def register_path(entry: KernelPath) -> KernelPath:
    """Register a path.  The name becomes a valid ``ExecutionPlan.path``,
    the candidates join every tuner enumeration, the artifact builder is
    called by ``schedule.build_schedule``, and the executors by
    ``SpmvOperator``."""
    _REGISTRY[entry.name] = entry
    register_path_name(entry.name)
    return entry


def get_path(name: str) -> KernelPath:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel path {name!r} registered "
                       f"(known: {sorted(_REGISTRY)})") from None


def registered_paths() -> Tuple[KernelPath, ...]:
    return tuple(_REGISTRY.values())


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def _always_feasible(plan, *, n, m, bandwidth) -> bool:
    return True


def _square_feasible(plan, *, n, m, bandwidth) -> bool:
    return n == m


def _onehot_window_fits(variant: str, w: int) -> bool:
    """The one-hot Pallas body keeps (W, 128) masks in VMEM: only windows
    up to ``ONEHOT_MAX_WINDOW`` compile for the chip."""
    from repro.kernels.csrc_spmv import ONEHOT_MAX_WINDOW
    return variant != "onehot" or w <= ONEHOT_MAX_WINDOW


def runs_pallas(plan) -> bool:
    """Whether the plan's local product runs a Pallas kernel (the one-hot
    variant of the windowed and nnz-split paths); every other form is
    plain XLA."""
    return (plan.path in ("kernel", "flat", "nnzsplit")
            and plan.variant == "onehot")


def _windowed_feasible(plan, *, n, m, bandwidth) -> bool:
    """Square matrix whose padded window fits under the plan's cap — the
    bandwidth gate shared by the rectangular-grid and flat-grid kernels.
    An int16 index stream additionally needs the window (and its padding
    sentinel, index == W) to fit in 16 bits."""
    if n != m:
        return False
    w = kernel_window(plan.tm, bandwidth)
    if w > plan.w_cap or not _onehot_window_fits(plan.variant, w):
        return False
    return plan.index_dtype != "int16" or w + 1 <= 32767


def _no_artifact(M, plan, coloring=None) -> dict:
    return {}


def _save_nothing(sched):
    return {}, {}


def _load_nothing(meta, z) -> dict:
    return {}


def _empty_fields(plan) -> tuple:
    return ()


def _windowed_fields(plan) -> tuple:
    return (plan.tm, plan.w_cap, plan.k_step_sublanes, plan.index_dtype,
            plan.value_dtype)


def _windowed_candidates(path, stats, space):
    out = []
    if stats.n != stats.m:
        return out
    for tm in space.tms:
        w = kernel_window(tm, stats.bandwidth)
        if w > space.w_cap:
            continue
        for ks in space.k_steps_sublanes:
            for idt in space.index_dtypes:
                if idt == "int16" and w + 1 > 32767:
                    continue        # window overflows 16-bit offsets
                for vdt in space.value_dtypes:
                    if (vdt == "bfloat16"
                            and not stats.numerically_symmetric):
                        # bf16 value streams are proposed only for the
                        # numerically-symmetric (well-conditioned) classes
                        continue
                    for var in space.variants:
                        if not _onehot_window_fits(var, w):
                            continue
                        out.append(ExecutionPlan(
                            path=path, tm=tm, w_cap=space.w_cap,
                            k_step_sublanes=ks, index_dtype=idt,
                            value_dtype=vdt, variant=var,
                            partition=space.partition,
                            accumulation=space.accumulation))
    return out


# ---------------------------------------------------------------------------
# 'segment' — segment-sum jnp path (any matrix, incl. the rectangular tail)
# ---------------------------------------------------------------------------

def _segment_candidates(stats, space):
    return [ExecutionPlan(path="segment", w_cap=space.w_cap,
                          partition=space.partition,
                          accumulation=space.accumulation)]


def _segment_make_spmv(M, schedule, plan, *, interpret=None, coloring=None):
    from repro.kernels import ref
    return lambda x: ref.csrc_spmv(M, x)


def _segment_make_spmm(M, schedule, plan, *, interpret=None, coloring=None):
    from repro.kernels import ref
    return lambda X: ref.csrc_spmm(M, X)


register_path(KernelPath(
    name="segment",
    feasible=_always_feasible,
    candidates=_segment_candidates,
    artifact_fields=_empty_fields,
    build_artifact=_no_artifact,
    save_artifact=_save_nothing,
    load_artifact=_load_nothing,
    make_spmv=_segment_make_spmv,
    make_spmm=_segment_make_spmm,
))


# ---------------------------------------------------------------------------
# 'ell' — row-padded CSRC product: the row's own term as a dense reduction,
# the transpose term as the one scatter-add (square matrices)
# ---------------------------------------------------------------------------

# Padding gate: the planes hold n·W slots (W = the most lower slots of any
# row); above this multiple of k the padding costs more than the row
# term's gather and scatter-add it saves, and the path is neither
# proposed nor built.
ELL_PAD_MAX = 1.5


def ell_padding_ok(n: int, width: int, k: int) -> bool:
    return n * width <= ELL_PAD_MAX * k


def _ell_candidates(stats, space):
    if stats.n != stats.m or not ell_padding_ok(stats.n,
                                                stats.lower_row_max,
                                                stats.k):
        return []
    return [ExecutionPlan(path="ell", w_cap=space.w_cap,
                          partition=space.partition,
                          accumulation=space.accumulation)]


def _ell_build(M, plan, coloring=None) -> dict:
    import numpy as np
    from repro.kernels import csrc_spmv_ell as ell_mod
    if not M.is_square:
        raise ValueError(
            "ell path pads the square CSRC part only; "
            "use 'segment' for rectangular matrices")
    width = int(np.diff(np.asarray(M.ia)).max(initial=0))
    if not ell_padding_ok(M.n, width, M.k):
        raise ValueError(f"ell planes of {M.n}x{width} pad more than "
                         f"{ELL_PAD_MAX}x the {M.k} lower slots")
    BUILD_COUNTS.inc("ell_pack")
    return {"ell_pack": ell_mod.pack_ell(M)}


def _ell_save(sched):
    import numpy as np
    pk = sched.ell_pack
    meta = {"ell_pack": {"n": pk.n, "width": pk.width}}
    arrays = dict(ell_ja=np.asarray(pk.ja), ell_al=np.asarray(pk.al),
                  ell_plane_of_slot=np.asarray(pk.plane_of_slot))
    if pk.au is not None:
        arrays["ell_au"] = np.asarray(pk.au)
    return meta, arrays


def _ell_load(meta, z) -> dict:
    import jax.numpy as jnp
    from repro.kernels.csrc_spmv_ell import EllPack
    pm = meta["ell_pack"]
    files = getattr(z, "files", z)
    return {"ell_pack": EllPack(
        n=pm["n"], width=pm["width"], ja=jnp.asarray(z["ell_ja"]),
        al=jnp.asarray(z["ell_al"]),
        au=jnp.asarray(z["ell_au"]) if "ell_au" in files else None,
        plane_of_slot=jnp.asarray(z["ell_plane_of_slot"]))}


def _ell_refresh(M, sched) -> dict:
    from repro.kernels import csrc_spmv_ell as ell_mod
    return {"ell_pack": ell_mod.refresh_ell_values(sched.ell_pack, M)}


def _ell_make_spmv(M, schedule, plan, *, interpret=None, coloring=None):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return functools.partial(ell_mod.ell_spmv, schedule.ell_pack, M.ad)


def _ell_make_spmm(M, schedule, plan, *, interpret=None, coloring=None):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return functools.partial(ell_mod.ell_spmm, schedule.ell_pack, M.ad)


def _ell_layout_classes():
    from repro.kernels.csrc_spmv_ell import EllHalo, EllShards
    return {"ell_shards": EllShards, "ell_halo": EllHalo}


def _ell_pack_shards(M, starts, plan):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return ell_mod.pack_ell_shards(M, starts)


def _ell_pack_halo(M, p, plan):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return ell_mod.pack_ell_halo(M, p)


def _ell_refresh_shards(lay, M, starts):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return ell_mod.refresh_ell_shards(lay, M, starts)


def _ell_refresh_halo(lay, M):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return ell_mod.refresh_ell_halo(lay, M)


def _ell_shard_arrays(lay):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return ell_mod.ell_shard_arrays(lay)


def _ell_local_fn(lay, n_local, interpret, variant):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return ell_mod.ell_local_fn(lay, n_local)


def _ell_halo_dims(lay):
    from repro.kernels import csrc_spmv_ell as ell_mod
    return ell_mod.ell_halo_dims(lay)


# each shard's rows as row-padded planes (kernels/csrc_spmv_ell.py): no
# plan field shapes them, so the layouts are keyed by matrix and rows only
ELL_SHARD_SUPPORT = ShardSupport(
    shards_kind="ell_shards",
    halo_kind="ell_halo",
    layout_classes=_ell_layout_classes,
    geometry=_empty_fields,
    pack_shards=_ell_pack_shards,
    pack_halo=_ell_pack_halo,
    refresh_shards=_ell_refresh_shards,
    refresh_halo=_ell_refresh_halo,
    shard_arrays=_ell_shard_arrays,
    local_fn=_ell_local_fn,
    halo_dims=_ell_halo_dims,
)


register_path(KernelPath(
    name="ell",
    feasible=_square_feasible,
    candidates=_ell_candidates,
    artifact_fields=_empty_fields,
    build_artifact=_ell_build,
    save_artifact=_ell_save,
    load_artifact=_ell_load,
    make_spmv=_ell_make_spmv,
    make_spmm=_ell_make_spmm,
    refresh_values=_ell_refresh,
    shard_support=ELL_SHARD_SUPPORT,
))


# ---------------------------------------------------------------------------
# 'kernel' — rectangular-grid block-ELL Pallas kernel (banded matrices)
# ---------------------------------------------------------------------------

def _index_dtype_of(plan):
    import jax.numpy as jnp
    return jnp.int16 if plan.index_dtype == "int16" else jnp.int32


def _value_dtype_of(plan):
    import jax.numpy as jnp
    return (jnp.bfloat16 if plan.value_dtype == "bfloat16"
            else jnp.float32)


def _kernel_build(M, plan, coloring=None) -> dict:
    from . import blockell
    if not M.is_square:
        raise ValueError(
            "kernel path packs the square CSRC part only; "
            "use 'segment' for rectangular matrices")
    BUILD_COUNTS.inc("pack")
    return {"pack": blockell.pack(M, tm=plan.tm, k_step=plan.k_step,
                                  w_cap=plan.w_cap,
                                  dtype=_value_dtype_of(plan),
                                  index_dtype=_index_dtype_of(plan))}


def _kernel_save(sched):
    import numpy as np
    pk = sched.pack
    # value streams are persisted as float32 (bf16 -> f32 widening is
    # lossless; numpy npz has no native bfloat16) and re-narrowed on load
    # to the dtype recorded in the meta
    meta = {"pack": {"n": pk.n, "tm": pk.tm, "nt": pk.nt,
                     "w_pad": pk.w_pad, "s": pk.s,
                     "num_symmetric": bool(pk.num_symmetric),
                     "value_dtype": str(pk.vals_l.dtype),
                     "pad_ratio": pk.pad_ratio}}
    arrays = dict(
        pack_vals_l=np.asarray(pk.vals_l, dtype=np.float32),
        pack_vals_u=np.asarray(pk.vals_u, dtype=np.float32),
        pack_col_local=np.asarray(pk.col_local),
        pack_row_in_win=np.asarray(pk.row_in_win),
        pack_ad=np.asarray(pk.ad, dtype=np.float32),
    )
    return meta, arrays


def _kernel_load(meta, z) -> dict:
    import jax.numpy as jnp
    from .blockell import BlockEll
    pm = meta["pack"]
    vdt = jnp.dtype(pm.get("value_dtype", "float32"))
    return {"pack": BlockEll(
        n=pm["n"], tm=pm["tm"], nt=pm["nt"], w_pad=pm["w_pad"], s=pm["s"],
        vals_l=jnp.asarray(z["pack_vals_l"], dtype=vdt),
        vals_u=jnp.asarray(z["pack_vals_u"], dtype=vdt),
        col_local=jnp.asarray(z["pack_col_local"]),
        row_in_win=jnp.asarray(z["pack_row_in_win"]),
        ad=jnp.asarray(z["pack_ad"], dtype=vdt),
        num_symmetric=bool(pm["num_symmetric"]),
        pad_ratio=float(pm["pad_ratio"]),
    )}


def _kernel_refresh(M, sched) -> dict:
    from . import blockell
    return {"pack": blockell.refresh_values(sched.pack, M)}


def _kernel_make_spmv(M, schedule, plan, *, interpret=None, coloring=None):
    if plan.variant == "stream":
        from repro.kernels import csrc_spmv_stream as stream_mod
        return functools.partial(stream_mod.blockell_spmv_stream,
                                 schedule.pack)
    from repro.kernels import csrc_spmv as kernel_mod
    return functools.partial(kernel_mod.blockell_spmv, schedule.pack,
                             interpret=interpret,
                             k_step_sublanes=plan.k_step_sublanes)


def _kernel_make_spmm(M, schedule, plan, *, interpret=None, coloring=None):
    if plan.variant == "stream":
        from repro.kernels import csrc_spmv_stream as stream_mod
        return functools.partial(stream_mod.blockell_spmm_stream,
                                 schedule.pack)
    from repro.kernels import csrc_spmv as kernel_mod
    return functools.partial(kernel_mod.blockell_spmm, schedule.pack,
                             interpret=interpret,
                             k_step_sublanes=plan.k_step_sublanes)


register_path(KernelPath(
    name="kernel",
    feasible=_windowed_feasible,
    candidates=functools.partial(_windowed_candidates, "kernel"),
    artifact_fields=_windowed_fields,
    build_artifact=_kernel_build,
    save_artifact=_kernel_save,
    load_artifact=_kernel_load,
    make_spmv=_kernel_make_spmv,
    make_spmm=_kernel_make_spmm,
    refresh_values=_kernel_refresh,
))


# ---------------------------------------------------------------------------
# 'colorful' — the paper's §3.2 color-by-color permutation writes
# ---------------------------------------------------------------------------

def _colorful_candidates(stats, space):
    if (stats.n != stats.m or stats.n > space.colorful_max_n
            or stats.k == 0):
        return []
    return [ExecutionPlan(path="colorful", w_cap=space.w_cap,
                          partition=space.partition,
                          accumulation=space.accumulation,
                          coloring=provider)
            for provider in space.colorings]


def _colorful_fields(plan) -> tuple:
    # greedy and race colorings are different artifacts: the provider joins
    # the schedule cache key so the two never collide
    return (plan.coloring,)


def _colorful_build(M, plan, coloring=None) -> dict:
    from .coloring import color_rows
    from . import schedule as schedule_mod
    if not M.is_square:
        raise ValueError(
            "colorful path covers the square CSRC part only; "
            "use 'segment' for rectangular matrices")
    if coloring is None:
        BUILD_COUNTS.inc("coloring")
        col = color_rows(M, provider=plan.coloring)
    else:
        col = coloring
    slots, ptr = schedule_mod.color_slot_batches(M, col)
    return {"coloring": col, "color_slots": slots, "color_slot_ptr": ptr}


def _colorful_save(sched):
    import numpy as np
    col = sched.coloring
    meta = {"num_colors": int(col.num_colors),
            "coloring_provider": col.provider}
    arrays = dict(
        color_of_row=np.asarray(col.color_of_row),
        rows_by_color=np.asarray(col.rows_by_color),
        color_ptr=np.asarray(col.color_ptr),
        color_slots=np.asarray(sched.color_slots),
        color_slot_ptr=np.asarray(sched.color_slot_ptr),
    )
    # RACE level-group metadata rides along so a loaded schedule keeps the
    # chunk-aware conflict invariant verifiable without re-coloring
    if col.level_of_row is not None:
        arrays["color_level_of_row"] = np.asarray(col.level_of_row)
    if col.group_of_row is not None:
        arrays["color_group_of_row"] = np.asarray(col.group_of_row)
    return meta, arrays


def _colorful_load(meta, z) -> dict:
    from .coloring import Coloring
    files = getattr(z, "files", z)
    return {
        "coloring": Coloring(
            color_of_row=z["color_of_row"],
            num_colors=int(meta["num_colors"]),
            rows_by_color=z["rows_by_color"],
            color_ptr=z["color_ptr"],
            provider=meta.get("coloring_provider", "greedy"),
            level_of_row=(z["color_level_of_row"]
                          if "color_level_of_row" in files else None),
            group_of_row=(z["color_group_of_row"]
                          if "color_group_of_row" in files else None)),
        "color_slots": z["color_slots"],
        "color_slot_ptr": z["color_slot_ptr"],
    }


def _colorful_make(M, schedule, plan, *, interpret=None, coloring=None):
    from . import schedule as schedule_mod
    slots, ptr = schedule.color_slots, schedule.color_slot_ptr
    if coloring is not None and coloring is not schedule.coloring:
        slots, ptr = schedule_mod.color_slot_batches(M, coloring)
    elif slots is None:
        slots, ptr = schedule_mod.color_slot_batches(M, schedule.coloring)
    return functools.partial(schedule_mod.colorful_apply, M,
                             color_slots=slots, color_slot_ptr=ptr)


register_path(KernelPath(
    name="colorful",
    feasible=_square_feasible,
    candidates=_colorful_candidates,
    artifact_fields=_colorful_fields,
    build_artifact=_colorful_build,
    save_artifact=_colorful_save,
    load_artifact=_colorful_load,
    make_spmv=_colorful_make,
    make_spmm=_colorful_make,       # colorful_apply handles (m,) and (m, r)
))


# ---------------------------------------------------------------------------
# 'flat' — flat-grid block-ELL Pallas kernel (skewed row-length matrices)
# ---------------------------------------------------------------------------

# Candidate gate: coefficient of variation of nnz-per-row above which the
# rectangular grid's per-tile padding is expected to waste bandwidth and
# the flat grid becomes worth measuring.  (Feasibility — can the matrix be
# tiled at all — is _windowed_feasible, identical to the rectangular
# kernel; the skew statistic only gates *enumeration*.)
FLAT_SKEW_MIN = 0.25


def flat_worth_measuring(stats) -> bool:
    """The flat enumerator's skew gate, shared with the tests: is the
    nnz-per-row spread large enough that per-tile-exact packing could
    beat the rectangular grid?"""
    return stats.nnz_row_dev > FLAT_SKEW_MIN * max(stats.nnz_row_mean, 1.0)


def _flat_candidates(stats, space):
    if not flat_worth_measuring(stats):
        return []
    return _windowed_candidates("flat", stats, space)


def _flat_build(M, plan, coloring=None) -> dict:
    from repro.kernels import csrc_spmv_flat as flat_mod
    if not M.is_square:
        raise ValueError(
            "flat path packs the square CSRC part only; "
            "use 'segment' for rectangular matrices")
    BUILD_COUNTS.inc("flat_pack")
    return {"flat_pack": flat_mod.pack_flat(
        M, tm=plan.tm, ks=plan.k_step_sublanes, w_cap=plan.w_cap,
        dtype=_value_dtype_of(plan),
        index_dtype=_index_dtype_of(plan))}


def _flat_save(sched):
    import numpy as np
    pk = sched.flat_pack
    meta = {"flat_pack": {"n": pk.n, "tm": pk.tm, "nt": pk.nt,
                          "w_pad": pk.w_pad,
                          "total_steps": pk.total_steps, "ks": pk.ks,
                          "num_symmetric": bool(pk.num_symmetric),
                          "value_dtype": str(pk.vals_l.dtype),
                          "pad_ratio": pk.pad_ratio}}
    arrays = dict(
        flat_vals_l=np.asarray(pk.vals_l, dtype=np.float32),
        flat_vals_u=np.asarray(pk.vals_u, dtype=np.float32),
        flat_col_local=np.asarray(pk.col_local),
        flat_row_in_win=np.asarray(pk.row_in_win),
        flat_ad=np.asarray(pk.ad, dtype=np.float32),
        flat_tile_of_step=np.asarray(pk.tile_of_step),
        flat_first_of_tile=np.asarray(pk.first_of_tile),
    )
    return meta, arrays


def _flat_load(meta, z) -> dict:
    import jax.numpy as jnp
    from repro.kernels.csrc_spmv_flat import FlatBlockEll
    pm = meta["flat_pack"]
    vdt = jnp.dtype(pm.get("value_dtype", "float32"))
    return {"flat_pack": FlatBlockEll(
        n=pm["n"], tm=pm["tm"], nt=pm["nt"], w_pad=pm["w_pad"],
        total_steps=pm["total_steps"], ks=pm["ks"],
        vals_l=jnp.asarray(z["flat_vals_l"], dtype=vdt),
        vals_u=jnp.asarray(z["flat_vals_u"], dtype=vdt),
        col_local=jnp.asarray(z["flat_col_local"]),
        row_in_win=jnp.asarray(z["flat_row_in_win"]),
        ad=jnp.asarray(z["flat_ad"], dtype=vdt),
        tile_of_step=jnp.asarray(z["flat_tile_of_step"]),
        first_of_tile=jnp.asarray(z["flat_first_of_tile"]),
        num_symmetric=bool(pm["num_symmetric"]),
        pad_ratio=float(pm["pad_ratio"]),
    )}


def _flat_refresh(M, sched) -> dict:
    from repro.kernels import csrc_spmv_flat as flat_mod
    return {"flat_pack": flat_mod.refresh_flat_values(sched.flat_pack, M)}


def _flat_make_spmv(M, schedule, plan, *, interpret=None, coloring=None):
    if plan.variant == "stream":
        from repro.kernels import csrc_spmv_stream as stream_mod
        return functools.partial(stream_mod.flat_spmv_stream,
                                 schedule.flat_pack)
    from repro.kernels import csrc_spmv_flat as flat_mod
    return functools.partial(flat_mod.flat_spmv, schedule.flat_pack,
                             interpret=interpret)


def _flat_make_spmm(M, schedule, plan, *, interpret=None, coloring=None):
    if plan.variant == "stream":
        from repro.kernels import csrc_spmv_stream as stream_mod
        return functools.partial(stream_mod.flat_spmm_stream,
                                 schedule.flat_pack)
    from repro.kernels import csrc_spmv_flat as flat_mod
    return functools.partial(flat_mod.flat_spmm, schedule.flat_pack,
                             interpret=interpret)


def _flat_layout_classes():
    from repro.kernels.csrc_spmv_flat import FlatHalo, FlatShards
    return {"flat_shards": FlatShards, "flat_halo": FlatHalo}


def _flat_geometry(plan):
    return (plan.tm, plan.k_step_sublanes, plan.w_cap, plan.index_dtype,
            plan.value_dtype)


def _flat_pack_shards(M, starts, plan):
    from repro.kernels import csrc_spmv_flat as flat_mod
    return flat_mod.pack_flat_shards(
        M, starts, tm=plan.tm, ks=plan.k_step_sublanes, w_cap=plan.w_cap,
        dtype=_value_dtype_of(plan), index_dtype=_index_dtype_of(plan))


def _flat_pack_halo(M, p, plan):
    from repro.kernels import csrc_spmv_flat as flat_mod
    return flat_mod.pack_flat_halo(
        M, p, tm=plan.tm, ks=plan.k_step_sublanes, w_cap=plan.w_cap,
        dtype=_value_dtype_of(plan), index_dtype=_index_dtype_of(plan))


def _flat_refresh_shards(lay, M, starts):
    from repro.kernels import csrc_spmv_flat as flat_mod
    return flat_mod.refresh_flat_shards(lay, M, starts)


def _flat_refresh_halo(lay, M):
    from repro.kernels import csrc_spmv_flat as flat_mod
    return flat_mod.refresh_flat_halo(lay, M)


def _flat_shard_arrays(lay):
    from repro.kernels import csrc_spmv_flat as flat_mod
    return flat_mod.flat_shard_arrays(lay)


def _flat_local_fn(lay, n_local, interpret, variant):
    from repro.kernels import csrc_spmv_flat as flat_mod
    return flat_mod.flat_local_fn(lay, n_local, interpret, variant)


def _flat_halo_dims(lay):
    from repro.kernels import csrc_spmv_flat as flat_mod
    return flat_mod.flat_halo_dims(lay)


FLAT_SHARD_SUPPORT = ShardSupport(
    shards_kind="flat_shards",
    halo_kind="flat_halo",
    layout_classes=_flat_layout_classes,
    geometry=_flat_geometry,
    pack_shards=_flat_pack_shards,
    pack_halo=_flat_pack_halo,
    refresh_shards=_flat_refresh_shards,
    refresh_halo=_flat_refresh_halo,
    shard_arrays=_flat_shard_arrays,
    local_fn=_flat_local_fn,
    halo_dims=_flat_halo_dims,
)


register_path(KernelPath(
    name="flat",
    feasible=_windowed_feasible,
    candidates=_flat_candidates,
    artifact_fields=_windowed_fields,
    build_artifact=_flat_build,
    save_artifact=_flat_save,
    load_artifact=_flat_load,
    make_spmv=_flat_make_spmv,
    make_spmm=_flat_make_spmm,
    refresh_values=_flat_refresh,
    shard_support=FLAT_SHARD_SUPPORT,
))


# ---------------------------------------------------------------------------
# 'nnzsplit' — merge-style equal-nnz chunking Pallas kernel (unstructured
# matrices: the CSRC analogue of merge-based CSR SpMV)
# ---------------------------------------------------------------------------

# Candidate gates.  The windowed paths lose in two distinct ways on
# unstructured matrices, and each gets a gate:
#  * skew: nnz-per-row CoV above this means even the flat grid's per-tile
#    packing pays for hub rows (power-law degree tails) — row-independent
#    chunking is worth measuring.  Deliberately above FLAT_SKEW_MIN: in
#    the moderate-skew band the flat path already wins and nnzsplit only
#    adds tuner work.
#  * spread: `ja` bandwidth above this fraction of n means the windowed
#    packs pad a window comparable to the whole matrix (random graphs,
#    circuits) — there is no band to exploit.
NNZSPLIT_SKEW_MIN = 2.0
NNZSPLIT_SPREAD_MIN = 0.25


def nnzsplit_worth_measuring(stats) -> bool:
    """The nnzsplit enumerator's gate, shared with the tests: is the
    matrix unstructured enough (heavy row-length tail OR non-banded column
    spread) that nnz-balanced chunking could beat the windowed paths?"""
    if stats.n != stats.m:
        return False
    cov = stats.nnz_row_dev / max(stats.nnz_row_mean, 1.0)
    return (cov > NNZSPLIT_SKEW_MIN
            or stats.bandwidth > NNZSPLIT_SPREAD_MIN * max(stats.n, 1))


def _nnzsplit_feasible(plan, *, n, m, bandwidth) -> bool:
    """Square matrices only; int16 gather indices additionally need every
    global index (src into x) to fit.  The per-chunk row window is checked
    at pack time against plan.w_cap (reused as the chunk-window cap) — it
    depends on row-gap statistics, not on the bandwidth stat."""
    if n != m:
        return False
    return plan.index_dtype != "int16" or n <= 32767


def _nnzsplit_candidates(stats, space):
    if not nnzsplit_worth_measuring(stats):
        return []
    out = []
    for ks in space.nnzsplit_ks:
        for idt in space.index_dtypes:
            if idt == "int16" and stats.n > 32767:
                continue        # gather index overflows 16 bits
            for vdt in space.value_dtypes:
                if (vdt == "bfloat16"
                        and not stats.numerically_symmetric):
                    continue
                for var in space.variants:
                    out.append(ExecutionPlan(
                        path="nnzsplit", w_cap=space.w_cap,
                        k_step_sublanes=ks, index_dtype=idt,
                        value_dtype=vdt, variant=var,
                        partition=space.partition,
                        accumulation=space.accumulation))
    return out


def _nnzsplit_fields(plan) -> tuple:
    # no tm: the chunking is row-independent; w_cap doubles as the
    # per-chunk row-window cap
    return (plan.k_step_sublanes, plan.w_cap, plan.index_dtype,
            plan.value_dtype)


def _nnzsplit_build(M, plan, coloring=None) -> dict:
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    if not M.is_square:
        raise ValueError(
            "nnzsplit path chunks the square CSRC part only; "
            "use 'segment' for rectangular matrices")
    BUILD_COUNTS.inc("nnzsplit_pack")
    return {"nnzsplit_pack": nz_mod.pack_nnzsplit(
        M, ks=plan.k_step_sublanes, r_cap=plan.w_cap,
        dtype=_value_dtype_of(plan),
        index_dtype=_index_dtype_of(plan))}


def _nnzsplit_save(sched):
    import numpy as np
    pk = sched.nnzsplit_pack
    meta = {"nnzsplit_pack": {
        "n": pk.n, "num_chunks": pk.num_chunks, "ks": pk.ks,
        "r_pad": pk.r_pad, "num_symmetric": bool(pk.num_symmetric),
        "value_dtype": str(pk.vals.dtype),
        "pad_ratio": pk.pad_ratio}}
    arrays = dict(
        nnzsplit_vals=np.asarray(pk.vals, dtype=np.float32),
        nnzsplit_lrow=np.asarray(pk.lrow),
        nnzsplit_src=np.asarray(pk.src),
        nnzsplit_chunk_row0=np.asarray(pk.chunk_row0),
        nnzsplit_fixup_idx=np.asarray(pk.fixup_idx),
        nnzsplit_ad=np.asarray(pk.ad, dtype=np.float32),
    )
    return meta, arrays


def _nnzsplit_load(meta, z) -> dict:
    import jax.numpy as jnp
    from repro.kernels.csrc_spmv_nnzsplit import NnzSplitPack
    pm = meta["nnzsplit_pack"]
    vdt = jnp.dtype(pm.get("value_dtype", "float32"))
    return {"nnzsplit_pack": NnzSplitPack(
        n=pm["n"], num_chunks=pm["num_chunks"], ks=pm["ks"],
        r_pad=pm["r_pad"],
        vals=jnp.asarray(z["nnzsplit_vals"], dtype=vdt),
        lrow=jnp.asarray(z["nnzsplit_lrow"]),
        src=jnp.asarray(z["nnzsplit_src"]),
        chunk_row0=jnp.asarray(z["nnzsplit_chunk_row0"]),
        fixup_idx=jnp.asarray(z["nnzsplit_fixup_idx"]),
        ad=jnp.asarray(z["nnzsplit_ad"], dtype=vdt),
        num_symmetric=bool(pm["num_symmetric"]),
        pad_ratio=float(pm["pad_ratio"]),
    )}


def _nnzsplit_refresh(M, sched) -> dict:
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    return {"nnzsplit_pack": nz_mod.refresh_nnzsplit_values(
        sched.nnzsplit_pack, M)}


def _nnzsplit_onehot_fits(pack, plan):
    # the chunk row window is known only once packed; one that does not
    # fit the one-hot body is a pack-time infeasibility, like w_cap
    if not _onehot_window_fits(plan.variant, pack.r_pad):
        raise ValueError(f"chunk row window {pack.r_pad} too wide for "
                         "the one-hot kernel")


def _nnzsplit_make_spmv(M, schedule, plan, *, interpret=None, coloring=None):
    if plan.variant == "stream":
        from repro.kernels import csrc_spmv_stream as stream_mod
        return functools.partial(stream_mod.nnzsplit_spmv_stream,
                                 schedule.nnzsplit_pack)
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    _nnzsplit_onehot_fits(schedule.nnzsplit_pack, plan)
    return functools.partial(nz_mod.nnzsplit_spmv, schedule.nnzsplit_pack,
                             interpret=interpret)


def _nnzsplit_make_spmm(M, schedule, plan, *, interpret=None, coloring=None):
    if plan.variant == "stream":
        from repro.kernels import csrc_spmv_stream as stream_mod
        return functools.partial(stream_mod.nnzsplit_spmm_stream,
                                 schedule.nnzsplit_pack)
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    _nnzsplit_onehot_fits(schedule.nnzsplit_pack, plan)
    return functools.partial(nz_mod.nnzsplit_spmm, schedule.nnzsplit_pack,
                             interpret=interpret)


def _nnzsplit_layout_classes():
    from repro.kernels.csrc_spmv_nnzsplit import NnzSplitHalo, NnzSplitShards
    return {"nnzsplit_shards": NnzSplitShards, "nnzsplit_halo": NnzSplitHalo}


def _nnzsplit_geometry(plan):
    return (plan.k_step_sublanes, plan.w_cap, plan.index_dtype,
            plan.value_dtype)


def _nnzsplit_pack_shards(M, starts, plan):
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    return nz_mod.pack_nnzsplit_shards(
        M, starts, ks=plan.k_step_sublanes, r_cap=plan.w_cap,
        dtype=_value_dtype_of(plan), index_dtype=_index_dtype_of(plan))


def _nnzsplit_pack_halo(M, p, plan):
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    return nz_mod.pack_nnzsplit_halo(
        M, p, ks=plan.k_step_sublanes, r_cap=plan.w_cap,
        dtype=_value_dtype_of(plan), index_dtype=_index_dtype_of(plan))


def _nnzsplit_refresh_shards(lay, M, starts):
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    return nz_mod.refresh_nnzsplit_shards(lay, M, starts)


def _nnzsplit_refresh_halo(lay, M):
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    return nz_mod.refresh_nnzsplit_halo(lay, M)


def _nnzsplit_shard_arrays(lay):
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    return nz_mod.nnzsplit_shard_arrays(lay)


def _nnzsplit_local_fn(lay, n_local, interpret, variant):
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    return nz_mod.nnzsplit_local_fn(lay, n_local, interpret, variant)


def _nnzsplit_halo_dims(lay):
    from repro.kernels import csrc_spmv_nnzsplit as nz_mod
    return nz_mod.nnzsplit_halo_dims(lay)


NNZSPLIT_SHARD_SUPPORT = ShardSupport(
    shards_kind="nnzsplit_shards",
    halo_kind="nnzsplit_halo",
    layout_classes=_nnzsplit_layout_classes,
    geometry=_nnzsplit_geometry,
    pack_shards=_nnzsplit_pack_shards,
    pack_halo=_nnzsplit_pack_halo,
    refresh_shards=_nnzsplit_refresh_shards,
    refresh_halo=_nnzsplit_refresh_halo,
    shard_arrays=_nnzsplit_shard_arrays,
    local_fn=_nnzsplit_local_fn,
    halo_dims=_nnzsplit_halo_dims,
)


register_path(KernelPath(
    name="nnzsplit",
    feasible=_nnzsplit_feasible,
    candidates=_nnzsplit_candidates,
    artifact_fields=_nnzsplit_fields,
    build_artifact=_nnzsplit_build,
    save_artifact=_nnzsplit_save,
    load_artifact=_nnzsplit_load,
    make_spmv=_nnzsplit_make_spmv,
    make_spmm=_nnzsplit_make_spmm,
    refresh_values=_nnzsplit_refresh,
    shard_support=NNZSPLIT_SHARD_SUPPORT,
))
