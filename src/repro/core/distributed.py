"""Distributed CSRC SpMV/SpMM: the paper's partitioning strategies on a JAX
mesh.

The paper parallelizes over OpenMP threads on 2–4 cores; we parallelize over
mesh shards (chips).  The race on the destination vector is identical — the
scatter term writes rows owned by other shards — and each of the paper's
accumulation strategies maps onto one collective pattern (docs/DESIGN.md §2):

  strategy='allreduce'       paper: local buffers + *all-in-one* accumulation.
      Every shard owns an nnz-balanced contiguous slot range, computes a
      full-length partial y, and the partials are summed with psum
      (all-reduce).  Output replicated.  Collective bytes: Θ(n) per shard.

  strategy='reduce_scatter'  paper: *per buffer / interval* accumulation.
      Same partials; psum_scatter sums them AND splits y into p equal
      intervals, one per shard — the paper's interval boundaries realized by
      the collective's shard boundaries.  Output row-sharded.  Θ(n/p) bytes.

  strategy='halo'            paper: *effective* accumulation.
      Row-block shards; because CSRC stores the lower triangle of a band
      matrix, a shard's effective write range is its own rows plus a window
      of at most `band` rows below — exchanged with the left neighbor via
      collective_permute.  Θ(band) bytes per shard, independent of n.
      This is the strategy the paper found best (80–93% of matrices), and
      on TPU the gap widens: ICI halo exchange is point-to-point.

All structure precomputations (row partition, shard slot layouts, halo
geometry) come from the schedule layer (core/schedule.py) — the builders
here contain no inline partition/pack construction and accept a cached
:class:`~repro.core.schedule.SpmvSchedule` so repeated builds (serving,
solver restarts) are zero-precompute.  Every strategy accepts x of shape
(n,) or (n, B): the multi-RHS product shares one collective per block.

Shard-local compute is itself plan-driven: with a plan (or schedule) whose
path registers a :class:`~repro.core.paths.ShardSupport` ('ell', 'flat',
'nnzsplit'), every strategy runs that path's product per shard —
allreduce/reduce_scatter over per-shard global-coordinate sub-packs
(``schedule.build_path_shards``), halo over local-coordinate per-shard
packs (``schedule.build_path_halo``) — instead of the default
segment-sum.  The branches below only consume the ShardSupport hooks;
a newly registered path is served here with zero edits.

The colorful method (paper §3.2) is a shared-memory construct (conflict-free
concurrent writes to one y); across distributed memories every write is a
message regardless of conflicts, so it degenerates to one of the above.  It
is provided on-device in kernels/ (see ref.colorful_spmv) and benchmarked
single-chip, as in the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro import obs

from .csrc import CSRC, bandwidth
from .plan import ExecutionPlan
from . import paths as paths_mod
from . import schedule as schedule_mod
from .schedule import SpmvSchedule


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def make_mesh(p: int, axis: str = "rows", devices=None) -> Mesh:
    """The 1-D mesh every builder here runs on.  Its axis is ``Auto``:
    the builders place shard arrays with NamedSharding and constrain the
    padded x inside jit, which ``Explicit`` axes (``jax.make_mesh``'s
    default) refuse."""
    return jax.make_mesh((p,), (axis,), axis_types=(AxisType.Auto,),
                         devices=devices)


def _bc(v: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Broadcast per-slot/per-row values over RHS columns when x is (n, B)."""
    return v[:, None] if x.ndim == 2 else v


def _place(arrays, mesh: Mesh, spec: P):
    """The shard layout's arrays put on the mesh, their bytes counted in
    ``mesh_place_bytes_total{site="layout"}``."""
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(arrays))
    obs.count("mesh_place_bytes_total", nbytes, site="layout")
    return jax.device_put(arrays, jax.sharding.NamedSharding(mesh, spec))


def _schedule(M: CSRC, p: int, accumulation: str,
              schedule: Optional[SpmvSchedule], cache,
              plan: Optional[ExecutionPlan] = None) -> SpmvSchedule:
    if schedule is not None:
        return schedule
    if plan is None:
        plan = ExecutionPlan(path="segment", partition="nnz",
                             accumulation=accumulation)
    return schedule_mod.schedule_for(M, plan, cache=cache, p=p)


def _shard_support(plan: Optional[ExecutionPlan]):
    """The requested plan's ShardSupport, or None when the path runs
    shard-locally as segment-sum ('segment', 'colorful', 'kernel', or no
    plan at all)."""
    if plan is None:
        return None
    return paths_mod.get_path(plan.path).shard_support


def build_spmv_allreduce(M: CSRC, mesh: Mesh, axis: str = "rows",
                         scatter_output: bool = False,
                         schedule: Optional[SpmvSchedule] = None,
                         cache=None,
                         plan: Optional[ExecutionPlan] = None,
                         interpret=None,
                         layout=None) -> Callable:
    """'allreduce' (all-in-one) and 'reduce_scatter' (per-buffer/interval)
    strategies.  x replicated, shape (n,) or (n, B); output replicated or
    row-sharded.  With a plan/schedule whose path registers ShardSupport
    ('ell', 'flat', 'nnzsplit') the shard-local partial runs that path's
    product over the shard's sub-pack instead of segment-sum.

    ``layout`` injects a prebuilt (or value-refreshed) ShardedSlots /
    path shards layout; otherwise the schedule layer builds it — and,
    given ``cache``, serves it from / ships it to the PlanCache npz
    layer."""
    p = mesh.shape[axis]
    acc = "reduce_scatter" if scatter_output else "allreduce"
    # the requested plan decides shard-local compute; the *schedule* only
    # supplies the row partition here, so a shard-supported plan builds
    # its path-specific artifact per shard (build_path_shards), never the
    # unused full-matrix pack — schedule_for gets the path-free variant
    req_plan = plan if plan is not None else (
        schedule.plan if schedule is not None else None)
    if plan is not None and schedule is None and plan.path != "segment":
        plan = dataclasses.replace(plan, path="segment")
    sched = _schedule(M, p, acc, schedule, cache, plan=plan)
    part = sched.partition
    if part.p != p:
        raise ValueError(
            f"schedule partition is {part.p}-way, mesh axis {axis} has {p}")
    n = M.n
    n_pad = _round_up(n, p)
    sup = _shard_support(req_plan)

    def reduce_y(y, x_ndim):
        if scatter_output:
            pad = ((0, n_pad - n),) + ((0, 0),) * (x_ndim - 1)
            y = jnp.pad(y, pad)
            return jax.lax.psum_scatter(y, axis, scatter_dimension=0,
                                        tiled=True)
        return jax.lax.psum(y, axis)

    if sup is not None:
        fs = (layout if layout is not None
              else schedule_mod.build_path_shards(M, part, req_plan,
                                                  cache=cache))
        local_y = sup.local_fn(fs, M.n, interpret, req_plan.variant)

        def local(*args):
            x = args[-1]
            return reduce_y(local_y(*args), x.ndim)

        sharded = _place(sup.shard_arrays(fs), mesh, P(axis))
        in_specs = (P(axis),) * len(sharded) + (P(),)
    else:
        ss = (layout if layout is not None
              else schedule_mod.build_sharded_slots(M, part, cache=cache))

        def local(row_idx, ja, al, au, ad_shard, x):
            # shard-local partial: the paper's private y buffer
            y = _bc(ad_shard[0], x) * x
            y = y + jax.ops.segment_sum(_bc(al[0], x) * x[ja[0]],
                                        row_idx[0], num_segments=n)
            y = y + jax.ops.segment_sum(_bc(au[0], x) * x[row_idx[0]],
                                        ja[0], num_segments=n)
            return reduce_y(y, x.ndim)

        sharded = _place((ss.row_idx, ss.ja, ss.al, ss.au, ss.ad_shard),
                         mesh, P(axis, None))
        in_specs = (P(axis, None),) * 5 + (P(),)

    # x is replicated (P() leaves trailing dims unsharded), so one
    # shard_map serves both the (n,) and (n, B) forms.  The varying-axis
    # check is off on kernel-backed paths: pallas_call has no rule for it.
    fn = shard_map(
        local, mesh=mesh, in_specs=in_specs,
        out_specs=(P(axis) if scatter_output else P()),
        check_vma=sup is None)

    # the shard arrays are arguments, not constants baked into the program
    run = jax.jit(lambda ops, x: fn(*ops, x))

    def apply(x):
        return run(sharded, x)

    apply.operands = sharded        # the placed shard arrays, for checks
    return apply


def halo_shard_fn(local_y: Callable, axis: str, p: int, h: int) -> Callable:
    """The halo strategy's shard function around a path's shard-local
    product ``local_y(*shard_arrays, x_ext) -> y_ext``: the left
    neighbour's tail of x in, the halo rows of y out to it."""
    def local(*args):
        x_own = args[-1]
        # x halo from the LEFT neighbor: its tail h rows
        left_tail = jax.lax.ppermute(
            x_own[-h:], axis, [(i, (i + 1) % p) for i in range(p)])
        x_ext = jnp.concatenate([left_tail, x_own])  # rows [r0-h, r1)
        y_ext = local_y(*args[:-1], x_ext)
        # y halo to the LEFT neighbor (it owns rows [r0-h, r0))
        from_right = jax.lax.ppermute(
            y_ext[:h], axis, [(i, (i - 1) % p) for i in range(p)])
        return y_ext[h:].at[-h:].add(from_right)

    return local


def build_spmv_halo(M: CSRC, mesh: Mesh, axis: str = "rows",
                    schedule: Optional[SpmvSchedule] = None,
                    cache=None,
                    plan: Optional[ExecutionPlan] = None,
                    interpret=None,
                    layout=None) -> Callable:
    """'halo' (effective) strategy: x and y row-sharded; only band-width
    windows cross shard boundaries (two collective_permutes).

    The halo geometry depends on the mesh width, not on the plan's
    partition, so it is not part of the ``schedule`` artifact —
    ``build_halo_layout`` / ``build_path_halo`` memoize it per
    (matrix, p[, pack geometry]) and repeated builds are zero-precompute.
    With a plan/schedule whose path registers ShardSupport each shard
    runs that path's kernel over its local-coordinate pack instead of
    the scatter-add form."""
    p = mesh.shape[axis]
    plan = plan if plan is not None else (
        schedule.plan if schedule is not None else None)
    sup = _shard_support(plan)

    if sup is not None:
        lay = (layout if layout is not None
               else schedule_mod.build_path_halo(M, p, plan, cache=cache))
        ns, h, n_local = sup.halo_dims(lay)
        n = M.n
        n_pad = ns * p
        local = halo_shard_fn(
            sup.local_fn(lay, n_local, interpret, plan.variant), axis, p, h)
        sharded = _place(sup.shard_arrays(lay), mesh, P(axis))
        slot_specs = (P(axis),) * len(sharded)
    else:
        lay = (layout if layout is not None
               else schedule_mod.build_halo_layout(M, p, cache=cache))
        n, ns, h, n_pad = M.n, lay.ns, lay.h, lay.n_pad

        def local(row_loc, col_rel, al, au, ad, x_own):
            # x halo from the LEFT neighbor: its tail h rows
            left_tail = jax.lax.ppermute(
                x_own[-h:], axis, [(i, (i + 1) % p) for i in range(p)])
            x_ext = jnp.concatenate([left_tail, x_own])  # rows [r0-h, r1)
            row_loc, col_rel = row_loc[0], col_rel[0]
            al, au, ad = al[0], au[0], ad[0]
            y_ext = jnp.zeros((ns + h,) + x_own.shape[1:], jnp.float32)
            y_ext = y_ext.at[h + row_loc].add(
                _bc(al, x_own) * x_ext[col_rel])
            y_ext = y_ext.at[col_rel].add(
                _bc(au, x_own) * x_ext[h + row_loc])
            y_ext = y_ext.at[h:].add(_bc(ad, x_own) * x_own)
            # y halo to the LEFT neighbor (it owns rows [r0-h, r0))
            from_right = jax.lax.ppermute(
                y_ext[:h], axis, [(i, (i - 1) % p) for i in range(p)])
            return y_ext[h:].at[-h:].add(from_right)

        sharded = _place((lay.row_loc, lay.col_rel, lay.al, lay.au, lay.ad),
                         mesh, P(axis, None))
        slot_specs = (P(axis, None),) * 5

    def make_fn(two_d: bool):
        x_spec = P(axis, None) if two_d else P(axis)
        # varying-axis check off on kernel-backed paths: pallas_call has
        # no rule for it
        return shard_map(
            local, mesh=mesh,
            in_specs=slot_specs + (x_spec,),
            out_specs=x_spec, check_vma=sup is None)

    fns = {False: make_fn(False), True: make_fn(True)}

    # the shard arrays are arguments, not constants baked into the program
    @jax.jit
    def run(ops, x):
        two_d = x.ndim == 2
        pad = ((0, n_pad - n),) + ((0, 0),) * (x.ndim - 1)
        x_pad = jnp.pad(x, pad)
        spec = P(axis, None) if two_d else P(axis)
        x_pad = jax.lax.with_sharding_constraint(
            x_pad, jax.sharding.NamedSharding(mesh, spec))
        y = fns[two_d](*ops, x_pad)
        return y[:n]

    def apply(x):
        return run(sharded, x)

    apply.operands = sharded        # the placed shard arrays, for checks
    return apply


STRATEGIES = ("allreduce", "reduce_scatter", "halo")


def build_sharded_spmv(M: CSRC, mesh: Mesh, axis: str = "rows",
                       strategy: str = "auto",
                       schedule: Optional[SpmvSchedule] = None,
                       cache=None,
                       plan: Optional[ExecutionPlan] = None,
                       interpret=None,
                       layout=None) -> Callable:
    """Factory: y_fn(x) computing A·x (or A·X for (n, B) blocks) across the
    mesh axis.  ``schedule``/``cache`` reuse the precomputed artifact; with
    ``strategy='auto'`` a supplied schedule's (or ``plan``'s) accumulation
    decides.  A plan/schedule whose path registers ShardSupport ('ell',
    'flat', 'nnzsplit') makes every strategy run that path's product
    shard-locally.
    ``layout`` injects a prebuilt shard layout (the serving MeshExecutor's
    value-refresh path)."""
    p = mesh.shape[axis]
    if strategy == "auto":
        if schedule is not None:
            strategy = schedule.plan.accumulation
        elif plan is not None:
            strategy = plan.accumulation
        else:
            ns = -(-M.n // p)
            strategy = ("halo" if bandwidth(M) <= max(8, ns)
                        else "reduce_scatter")
    if strategy == "allreduce":
        return build_spmv_allreduce(M, mesh, axis, scatter_output=False,
                                    schedule=schedule, cache=cache,
                                    plan=plan, interpret=interpret,
                                    layout=layout)
    if strategy == "reduce_scatter":
        return build_spmv_allreduce(M, mesh, axis, scatter_output=True,
                                    schedule=schedule, cache=cache,
                                    plan=plan, interpret=interpret,
                                    layout=layout)
    if strategy == "halo":
        return build_spmv_halo(M, mesh, axis, schedule=schedule,
                               cache=cache, plan=plan, interpret=interpret,
                               layout=layout)
    raise ValueError(f"unknown strategy {strategy!r}")


def collective_bytes_from_stats(n: int, band: int, p: int, strategy: str,
                                nrhs: int = 1) -> int:
    """The collective-bytes model over bare matrix statistics — the form
    the tuner's mesh-aware candidate gate consumes (no matrix needed)."""
    if strategy == "allreduce":
        return 2 * 4 * n * nrhs * (p - 1) // p       # ring all-reduce
    if strategy == "reduce_scatter":
        return 4 * n * nrhs * (p - 1) // p
    if strategy == "halo":
        return 2 * 4 * max(8, band) * nrhs           # x halo + y halo
    raise ValueError(strategy)


def collective_bytes_estimate(M: CSRC, p: int, strategy: str,
                              nrhs: int = 1) -> int:
    """Napkin model the mesh tuner gates on: bytes crossing
    links per shard per product (scales linearly with the RHS block)."""
    return collective_bytes_from_stats(M.n, bandwidth(M), p, strategy,
                                       nrhs=nrhs)
