"""Benchmark harness — one function per paper table/figure.

  fig5_sequential     CSR vs CSRC Mflop/s + loads-per-flop (paper Fig. 5)
  table2_accumulation accumulation-strategy cost (paper Table 2) — runs on
                      8 placeholder devices in a subprocess
  fig6_colorful       colorful vs local-buffers by band width (paper Fig. 6)
  fig89_scaling       speedup vs shard count (paper Figs. 8/9) — subprocess
  schedule_build      schedule/pack build time vs steady-state execute per
                      path (incl. colorful coloring quality) — also written
                      to results/BENCH_schedule.json
  coloring_quality    greedy vs RACE coloring providers: palette size,
                      balance, reuse-distance strides, colored-path
                      steady-state per-column time + cost-model pick on
                      band/skew/powerlaw rows and tri/tet element graphs —
                      written to results/BENCH_coloring.json (the CI
                      bench-smoke job asserts the RACE tet palette beats
                      greedy)
  flat_vs_rect        flat-grid vs rectangular-grid kernel on skewed and
                      uniform band matrices: pad_ratio, streamed_bytes,
                      SpMV/SpMM time — written to results/BENCH_flat.json
                      (the CI bench-smoke job asserts the skewed rows)
  nnzsplit_unstructured  nnz-split chunking vs the windowed grids on the
                      shuffled power-law class, tuned under a bandwidth
                      roofline model — written to
                      results/BENCH_nnzsplit.json (the CI bench-smoke job
                      asserts nnzsplit is selected and streams fewer
                      bytes than either windowed grid)
  assembly            FEM assembly (repro.assembly): per mesh generator,
                      every (strategy, variant) scatter executor —
                      fused colored-batch kernels (stream/onehot), the
                      per-color XLA baseline, sorted-slot, private
                      buffers, serial oracle — steady-state time +
                      predicted roofline fraction per row, plus the
                      tune_assembly winner and the assemble→tune→solve
                      pipeline — written to results/BENCH_assembly.json
                      (CI asserts bit-identity everywhere and that a
                      Pallas strategy beats the per-color baseline on
                      the tet mesh)
  serving             local vs mesh serving engines (repro.serve) on 8
                      forced host devices in a subprocess: mesh-aware
                      tuning of the per-(matrix, p) winner, register
                      (build) vs steady-state per-tick latency split —
                      written to results/BENCH_serving.json (the CI
                      serving-smoke job asserts the mesh rows exist)
  local_gap           streaming vs one-hot kernel variants on the suite's
                      windowed/unstructured classes: steady-state SpMV +
                      nrhs=8 SpMM per (path, variant) with the analytic
                      roofline fraction each achieved, the per-path
                      streaming speedup, and the regenerated local-vs-mesh
                      steady-state split — written to
                      results/BENCH_local_gap.json (the CI bench-smoke
                      job asserts streaming beats one-hot and that every
                      plan row carries roofline_fraction)
  roofline_summary    single-pod roofline table from results/dryrun (§Roofline)

Output: ``name,us_per_call,derived`` CSV rows.
Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""
import argparse
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import csrc, paths, schedule as schedule_mod, tuner
from repro.core.coloring import (balance_stats, color_rows, group_stats,
                                 reuse_stats, verify_coloring)
from repro.core.plan import ExecutionPlan
from repro.assembly import mesh as amesh
from repro.assembly.conflict import color_elements, verify_element_coloring
from repro.roofline import cost_model
from repro.kernels import ref, ops
from repro.runtime import enable_compile_cache
from benchmarks.util import steady_state, time_fn, row
from benchmarks.suite import matrices

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLAN_CACHE_PATH = os.path.join(ROOT, "results", "plans.json")
BENCH_SCHEDULE_PATH = os.path.join(ROOT, "results", "BENCH_schedule.json")
BENCH_FLAT_PATH = os.path.join(ROOT, "results", "BENCH_flat.json")
BENCH_NNZSPLIT_PATH = os.path.join(ROOT, "results", "BENCH_nnzsplit.json")
BENCH_ASSEMBLY_PATH = os.path.join(ROOT, "results", "BENCH_assembly.json")
BENCH_SERVING_PATH = os.path.join(ROOT, "results", "BENCH_serving.json")
BENCH_LOCAL_GAP_PATH = os.path.join(ROOT, "results", "BENCH_local_gap.json")
BENCH_COLORING_PATH = os.path.join(ROOT, "results", "BENCH_coloring.json")


# ---------------------------------------------------------------------------
# Fig. 5: sequential CSR vs CSRC
# ---------------------------------------------------------------------------

def fig5_sequential(small: bool):
    print("# fig5_sequential: CSR vs CSRC (single device)")
    rng = np.random.default_rng(0)
    for name, make in matrices(small):
        M = make()
        x = jnp.asarray(rng.standard_normal(M.m).astype(np.float32))
        r_idx, c_idx, vals = ref.csr_from_csrc(M)
        r_idx = jnp.asarray(r_idx)
        c_idx = jnp.asarray(c_idx)
        vals = jnp.asarray(vals)
        csr = jax.jit(lambda x: ref.csr_spmv_arrays(r_idx, c_idx, vals, x,
                                                    M.n))
        csrc_fn = ops.SpmvOperator(M, path="segment")
        t_csr = time_fn(csr, x)
        t_csrc = time_fn(csrc_fn, x)
        flops = 2 * M.nnz - M.n
        mflops_csr = flops / t_csr / 1e6
        mflops_csrc = flops / t_csrc / 1e6
        # paper §4.1 analytic loads/flops: CSR 1.5, CSRC ~1.26
        loads_csr = 3 * M.nnz
        loads_csrc = (5 * M.nnz // 2 - M.n // 2 if not M.numerically_symmetric
                      else 2 * M.nnz)
        row(f"fig5/{name}/csr", t_csr * 1e6,
            f"mflops={mflops_csr:.0f};loads_per_flop={loads_csr/flops:.2f}")
        row(f"fig5/{name}/csrc", t_csrc * 1e6,
            f"mflops={mflops_csrc:.0f};loads_per_flop={loads_csrc/flops:.2f};"
            f"speedup={t_csr/t_csrc:.2f}")


# ---------------------------------------------------------------------------
# Table 2: accumulation strategies (multi-device, subprocess)
# ---------------------------------------------------------------------------

_TABLE2_CODE = """
    import numpy as np, jax, jax.numpy as jnp, time
    from repro.core import csrc, distributed as D
    from benchmarks.util import time_fn
    mesh = D.make_mesh(8)
    # in-cache vs out-of-cache analogs (paper splits at ws ~ cache size)
    cases = [('small_ws', 4096, 16), ('large_ws', 200000, 16)]
    rng = np.random.default_rng(0)
    for label, n, band in cases:
        M = csrc.fem_band(n, band, seed=1)
        x = jnp.asarray(rng.standard_normal(M.n).astype(np.float32))
        for strat in ('allreduce', 'reduce_scatter', 'halo'):
            fn = D.build_sharded_spmv(M, mesh, 'rows', strat)
            t = time_fn(fn, x)
            cb = D.collective_bytes_estimate(M, 8, strat)
            print(f'table2/{label}/{strat},{t*1e6:.1f},'
                  f'collective_bytes_per_shard={cb}')
"""


def _cpu_child(code: str, timeout: int = 900) -> str:
    """Run ``code`` in a child pinned to the CPU backend with 8 forced
    host devices.  This parent has already touched JAX and, on a TPU
    host, holds the chip: a child that asked for it would fail or hang.
    So the forced-device sections measure the CPU, and say so."""
    print("# (child process: JAX_PLATFORMS=cpu, 8 forced host devices — "
          "CPU timings, not a device metric)")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + ":" + ROOT
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return out.stdout.strip()


def table2_accumulation(small: bool):
    print("# table2_accumulation: strategy cost on 8 shards "
          "(all-in-one=allreduce, interval=reduce_scatter, effective=halo)")
    code = _TABLE2_CODE
    if small:
        code = code.replace("200000", "20000")
    print(_cpu_child(code))


# ---------------------------------------------------------------------------
# Fig. 6: colorful vs local buffers
# ---------------------------------------------------------------------------

def fig6_colorful(small: bool):
    print("# fig6_colorful: colorful vs local-buffers by band width")
    rng = np.random.default_rng(0)
    n = 1000 if small else 4000
    for band in (1, 2, 8):
        M = csrc.fem_band(n, band, seed=band)
        x = jnp.asarray(rng.standard_normal(M.n).astype(np.float32))
        col = color_rows(M)
        colorful = ops.SpmvOperator(M, path="colorful", coloring=col)
        buffers = ops.SpmvOperator(M, path="segment")
        t_c = time_fn(colorful, x)
        t_b = time_fn(buffers, x)
        bs = balance_stats(col)
        row(f"fig6/band{band}/colorful", t_c * 1e6,
            f"colors={col.num_colors};balance={bs['imbalance']:.2f}")
        row(f"fig6/band{band}/local_buffers", t_b * 1e6,
            f"speedup_vs_colorful={t_c/t_b:.2f}")


# ---------------------------------------------------------------------------
# Figs. 8/9: scaling with shard count
# ---------------------------------------------------------------------------

_FIG89_CODE = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import csrc, distributed as D
    from repro.kernels import ops
    from benchmarks.util import time_fn
    rng = np.random.default_rng(0)
    n, band = NN, 16
    M = csrc.fem_band(n, band, seed=1)
    x = jnp.asarray(rng.standard_normal(M.n).astype(np.float32))
    seq = ops.SpmvOperator(M, path='segment')
    t1 = time_fn(seq, x)
    print(f'fig89/p1/sequential,{t1*1e6:.1f},speedup=1.00')
    for p in (2, 4, 8):
        mesh = D.make_mesh(p)
        fn = D.build_sharded_spmv(M, mesh, 'rows', 'halo')
        t = time_fn(fn, x)
        print(f'fig89/p{p}/halo,{t*1e6:.1f},speedup={t1/t:.2f}')
"""


def fig89_scaling(small: bool):
    print("# fig89_scaling: speedup vs shards (halo strategy, band FEM)")
    print(_cpu_child(_FIG89_CODE.replace("NN", "40000" if small
                                         else "400000")))


# ---------------------------------------------------------------------------
# Schedule build cost vs steady-state execution (the schedule layer)
# ---------------------------------------------------------------------------

def schedule_build(small: bool):
    """Precompute (schedule/pack/coloring build) time reported separately
    from steady-state execute time — previously the first timed call
    absorbed packing.  Colorful rows carry coloring quality (color count +
    rows-per-color balance) so coloring improvements show up directly.
    Rows are also written to results/BENCH_schedule.json."""
    print("# schedule_build: one-time precompute vs steady-state execute")
    rng = np.random.default_rng(0)
    records = []

    def bench_one(name, M, label, plan):
        x = jnp.asarray(rng.standard_normal(M.m).astype(np.float32))
        t0 = time.perf_counter()
        try:
            sched = schedule_mod.build_schedule(M, plan)
        except ValueError:
            return                      # infeasible path for this matrix
        t_build = time.perf_counter() - t0
        op = ops.SpmvOperator.from_plan(M, plan, schedule=sched)
        t_exec = time_fn(op, x)
        derived = f"build_us={t_build * 1e6:.1f}"
        if sched.coloring is not None:
            bs = balance_stats(sched.coloring)
            derived += (f";colors={sched.coloring.num_colors}"
                        f";balance={bs['imbalance']:.2f}")
        row(f"schedule/{name}/{label}", t_exec * 1e6, derived)
        records.append({"name": f"schedule/{name}/{label}",
                        "execute_us": round(t_exec * 1e6, 1),
                        "build_us": round(t_build * 1e6, 1),
                        "plan": plan.key(),
                        "derived": derived})

    for name, make in matrices(small):
        M = make()
        stats = tuner.stats_of(M)
        bench_one(name, M, "segment", ExecutionPlan(path="segment"))
        if M.is_square:
            bench_one(name, M, "kernel", ExecutionPlan(path="kernel"))
            if paths.flat_worth_measuring(stats):
                # same skew gate the tuner's flat enumerator uses
                bench_one(name, M, "flat", ExecutionPlan(path="flat"))
            if paths.nnzsplit_worth_measuring(stats):
                bench_one(name, M, "nnzsplit",
                          ExecutionPlan(path="nnzsplit"))
            if M.n <= 2048 and stats.bandwidth <= 64 and M.k > 0:
                bench_one(name, M, "colorful",
                          ExecutionPlan(path="colorful"))
    # dedicated colorful rows (paper Fig. 6 band classes): coloring quality
    # must stay visible even when the suite matrices outgrow the gate
    n = 1000 if small else 4000
    for band in (1, 2, 8):
        bench_one(f"colorful_band{band}", csrc.fem_band(n, band, seed=band),
                  "colorful", ExecutionPlan(path="colorful"))
    os.makedirs(os.path.dirname(BENCH_SCHEDULE_PATH), exist_ok=True)
    with open(BENCH_SCHEDULE_PATH, "w") as f:
        json.dump({"rows": records}, f, indent=1, sort_keys=True)
    print(f"# schedule_build: {len(records)} rows -> {BENCH_SCHEDULE_PATH}")


# ---------------------------------------------------------------------------
# Coloring providers: greedy first-fit vs RACE recursive level-groups
# ---------------------------------------------------------------------------

def coloring_quality(small: bool):
    """Greedy vs RACE coloring provider per matrix class: palette size,
    rows-per-color balance, reuse-distance strides, serial-chunk shape,
    the colored path's steady-state per-column time, and the cost-model
    prediction that drives the tuner's provider choice.  Element-graph
    rows (tri/tet meshes) cover the FEM assembly colorer, where the tet
    node cliques force any classic coloring past 24 colors while RACE's
    level groups stay at a handful.  Written to
    results/BENCH_coloring.json (the CI bench-smoke job asserts the RACE
    tet palette is below greedy and every provider row carries balance
    stats)."""
    print("# coloring_quality: greedy vs RACE coloring providers")
    rng = np.random.default_rng(0)
    records = []

    row_cases = [
        ("fem_band_wide", csrc.fem_band(600 if small else 2400, 24, seed=3)),
        ("skew_band", csrc.skewed_band(512 if small else 2048, 12, 2,
                                       seed=6)),
        ("powerlaw", csrc.powerlaw_laplacian(512 if small else 2048,
                                             seed=7)),
    ]
    for name, M in row_cases:
        x = jnp.asarray(rng.standard_normal(M.m).astype(np.float32))
        stats = tuner.stats_of(M)
        measured, predicted = {}, {}
        for provider in ("greedy", "race"):
            plan = ExecutionPlan(path="colorful", coloring=provider)
            col = color_rows(M, provider=provider)
            op = ops.SpmvOperator.from_plan(M, plan)
            t_exec = time_fn(op, x)
            est = cost_model.plan_cost(stats, plan)
            measured[provider] = t_exec
            predicted[provider] = est.predicted_s
            bs, rs, gs = balance_stats(col), reuse_stats(col), group_stats(
                col)
            derived = (f"colors={col.num_colors}"
                       f";balance={bs['imbalance']:.2f}"
                       f";mean_stride={rs['mean_stride']:.1f}"
                       f";predicted_us={est.predicted_s * 1e6:.1f}")
            row(f"coloring/{name}/{provider}", t_exec * 1e6, derived)
            records.append({
                "name": f"coloring/{name}/{provider}", "kind": "rows",
                "provider": provider, "colors": col.num_colors,
                "balance": bs, "reuse": rs, "groups": gs,
                "valid": bool(verify_coloring(M, col)),
                "execute_us": round(t_exec * 1e6, 2),
                "predicted_us": round(est.predicted_s * 1e6, 2)})
        # the tuner's predict-then-measure story per matrix: which provider
        # the roofline model picks, and which one actually won the clock
        records.append({
            "name": f"coloring/{name}/pick", "kind": "pick",
            "predicted_pick": min(predicted, key=predicted.get),
            "measured_pick": min(measured, key=measured.get)})

    el_cases = [
        ("tri", amesh.grid_tri(12 if small else 24)),
        ("tet", amesh.grid_tet(3 if small else 4)),
    ]
    for name, mesh in el_cases:
        for provider in ("greedy", "race"):
            col = color_elements(mesh.conn, provider=provider)
            bs, gs = balance_stats(col), group_stats(col)
            derived = (f"colors={col.num_colors}"
                       f";balance={bs['imbalance']:.2f}"
                       f";chunks={gs['chunks']}")
            row(f"coloring/{name}_elements/{provider}", 0.0, derived)
            records.append({
                "name": f"coloring/{name}_elements/{provider}",
                "kind": "elements", "provider": provider,
                "colors": col.num_colors, "balance": bs, "groups": gs,
                "valid": bool(verify_element_coloring(mesh.conn, col))})

    os.makedirs(os.path.dirname(BENCH_COLORING_PATH), exist_ok=True)
    with open(BENCH_COLORING_PATH, "w") as f:
        json.dump({"rows": records}, f, indent=1, sort_keys=True)
    print(f"# coloring_quality: {len(records)} rows -> "
          f"{BENCH_COLORING_PATH}")


# ---------------------------------------------------------------------------
# Flat-grid vs rectangular-grid kernel (the paper's padding-waste argument,
# measured: skewed row lengths defeat uniform ELL padding)
# ---------------------------------------------------------------------------

def flat_vs_rect(small: bool):
    """Rect block-ELL grid vs flat grid per matrix: pad_ratio and
    streamed_bytes (the bandwidth-bound cost the padding inflates) plus
    SpMV and nrhs=8 SpMM times.  On the skewed FEM class the flat grid
    must be strictly below on both pack metrics — the CI bench-smoke job
    asserts exactly that from results/BENCH_flat.json."""
    print("# flat_vs_rect: rectangular vs flat grid "
          "(pad_ratio / streamed_bytes / time)")
    rng = np.random.default_rng(0)
    n = 1024 if small else 4096
    cases = [
        ("skew_fem", csrc.skewed_band(n, 48, 3, wide_frac=0.06, seed=1)),
        ("uniform_band", csrc.fem_band(n, 8, seed=2, fill=1.0)),
    ]
    records = []
    for name, M in cases:
        x = jnp.asarray(rng.standard_normal(M.m).astype(np.float32))
        X = jnp.asarray(rng.standard_normal((M.m, 8)).astype(np.float32))
        per_path = {}
        for path in ("kernel", "flat"):
            plan = ExecutionPlan(path=path, tm=64)
            try:
                op = ops.SpmvOperator.from_plan(M, plan)
            except ValueError:
                continue                    # window over cap: skip matrix
            t = time_fn(op, x)
            t_mm = time_fn(op, X)
            per_path[path] = {
                "pad_ratio": round(float(op.pack.pad_ratio), 3),
                "streamed_bytes": int(op.pack.streamed_bytes()),
                "spmv_us": round(t * 1e6, 1),
                "spmm8_us": round(t_mm * 1e6, 1),
            }
            row(f"flat/{name}/{path}", t * 1e6,
                f"pad_ratio={op.pack.pad_ratio:.2f};"
                f"streamed_bytes={op.pack.streamed_bytes()};"
                f"spmm8_us={t_mm * 1e6:.1f}")
        if {"kernel", "flat"} <= set(per_path):
            rect, flat = per_path["kernel"], per_path["flat"]
            records.append({
                "matrix": name, "n": M.n, "nnz": M.nnz,
                "rect": rect, "flat": flat,
                "flat_wins_padding":
                    bool(flat["pad_ratio"] < rect["pad_ratio"]
                         and flat["streamed_bytes"]
                         < rect["streamed_bytes"]),
            })
    os.makedirs(os.path.dirname(BENCH_FLAT_PATH), exist_ok=True)
    with open(BENCH_FLAT_PATH, "w") as f:
        json.dump({"rows": records}, f, indent=1, sort_keys=True)
    print(f"# flat_vs_rect: {len(records)} rows -> {BENCH_FLAT_PATH}")


# ---------------------------------------------------------------------------
# Nnz-split chunking vs the windowed grids on the unstructured class
# ---------------------------------------------------------------------------

def nnzsplit_unstructured(small: bool):
    """The reason 'nnzsplit' exists, measured on the shuffled power-law
    Laplacian (hub rows, bandwidth ~ n): tuning runs under a bandwidth
    roofline model — modeled time = streamed bytes / effective bandwidth,
    with the irregular gather/scatter paths ('segment', 'colorful')
    charged a 4x effective-bandwidth penalty against the contiguous-
    stream kernels (the DRAM stream-vs-random-access gap in Schubert et
    al.'s SpMV bandwidth model, arXiv:1011.2308) — so the winner is
    decided by memory traffic, which interpret-mode wall clock cannot
    see.  The nnz-split row must win the class and stream strictly fewer
    bytes than either windowed grid; CI bench-smoke asserts both from
    results/BENCH_nnzsplit.json."""
    print("# nnzsplit_unstructured: nnz-split vs windowed grids "
          "(bandwidth-roofline tuning)")
    n = 2000            # windowed grids stay feasible (bandwidth < w_cap)
    M = csrc.powerlaw_laplacian(n, seed=7)
    stats = tuner.stats_of(M)
    assert paths.nnzsplit_worth_measuring(stats), "powerlaw not gated in?"

    BW = 100e9                       # arbitrary scale; only ratios matter

    def modeled(op, x):
        eff = BW / 4 if op.plan.path in ("segment", "colorful") else BW
        return op.bytes_per_call / eff

    cache = tuner.PlanCache()
    res = tuner.tune(M, cache=cache, measure=modeled)
    row(f"nnzsplit/powerlaw_{n}/winner",
        res.timings_s[res.plan.key()] * 1e6, f"plan={res.plan.key()};"
        f"candidates={len(res.timings_s)}")
    streamed = {}
    for path in ("nnzsplit", "flat", "kernel"):
        plan = (ExecutionPlan(path="nnzsplit", k_step_sublanes=2)
                if path == "nnzsplit" else ExecutionPlan(path=path, tm=64))
        try:
            op = ops.SpmvOperator.from_plan(M, plan)
        except ValueError:
            continue                      # window over cap: skip the grid
        streamed[path] = int(op.bytes_per_call)
        row(f"nnzsplit/powerlaw_{n}/{path}", modeled(op, None) * 1e6,
            f"streamed_bytes={op.bytes_per_call};"
            f"pad_ratio={op.pack.pad_ratio:.2f}")
    rec = {
        "matrix": f"powerlaw_{n}", "n": M.n, "nnz": M.nnz,
        "bandwidth": int(stats.bandwidth),
        "winner_plan": res.plan.key(),
        "nnzsplit_selected": res.plan.path == "nnzsplit",
        "streamed_bytes": streamed,
        "beats_windowed_bytes": bool(
            "nnzsplit" in streamed
            and all(streamed["nnzsplit"] < streamed[p]
                    for p in ("flat", "kernel") if p in streamed)),
    }
    os.makedirs(os.path.dirname(BENCH_NNZSPLIT_PATH), exist_ok=True)
    with open(BENCH_NNZSPLIT_PATH, "w") as f:
        json.dump({"rows": [rec]}, f, indent=1, sort_keys=True)
    print(f"# nnzsplit_unstructured: 1 row -> {BENCH_NNZSPLIT_PATH}")


# ---------------------------------------------------------------------------
# FEM assembly: colored vs private-buffer vs serial oracle
# ---------------------------------------------------------------------------

def assembly(small: bool):
    """Conflict-free CSRC assembly (repro.assembly): per mesh generator,
    the one-time AssemblySchedule build vs the per-step value scatter of
    every (strategy, variant) executor — the fused colored-batch Pallas
    kernels (stream/onehot), the legacy per-color XLA baseline, the
    sorted-slot single segment-sum, private buffers + reduce, and the
    serial numpy oracle — each row carrying its predicted roofline
    fraction.  Every executor must equal the oracle bit-for-bit (dyadic
    stiffness) and a fused kernel must beat the per-color baseline on
    the tet mesh — the CI assembly smoke asserts both from
    results/BENCH_assembly.json.  Ends with the tune_assembly winner per
    mesh and the assemble→tune→solve pipeline on the tri mesh."""
    from repro.assembly import (assembly_schedule_for, mesh as amesh,
                                scatter_colored, scatter_private,
                                scatter_serial, scatter_sorted,
                                tune_assembly, values_to_csrc)
    from repro.core.solvers import cg_solve

    print("# assembly: fused kernels vs per-color baseline vs serial "
          "oracle (build split from per-step scatter)")
    s = 12 if small else 40
    meshes = [(name, gen(s)) for name, gen in amesh.MESH_GENERATORS]
    records = []
    cache = tuner.PlanCache()
    combos = (("colored", "stream",
               lambda sc: jax.jit(lambda k: scatter_colored(sc, k))),
              ("colored", "onehot",
               lambda sc: jax.jit(
                   lambda k: scatter_colored(sc, k, variant="onehot"))),
              ("colored", "percolor",
               lambda sc: jax.jit(
                   lambda k: scatter_colored(sc, k, variant="percolor"))),
              ("sorted", "stream",
               lambda sc: jax.jit(lambda k: scatter_sorted(sc, k))),
              ("private", "vmap",
               lambda sc: jax.jit(lambda k: scatter_private(sc, k))))
    for name, mesh in meshes:
        ke = amesh.poisson_stiffness(mesh, mass=1.0)
        t0 = time.perf_counter()
        sched = assembly_schedule_for(mesh, cache=cache)
        t_build = time.perf_counter() - t0
        ref = scatter_serial(sched, ke)
        col = sched.coloring
        kej = jnp.asarray(ke)
        times, match = {}, {}
        for strategy, variant, make_fn in combos:
            key = f"{strategy}/{variant}"
            fn = make_fn(sched)
            t = steady_state(fn, kej, warmup=2, repeats=5,
                             name="assembly.scatter", matrix=name,
                             strategy=strategy, variant=variant)
            vals = np.asarray(fn(kej))
            times[key] = t
            match[key] = bool(np.array_equal(vals, ref))
            est = cost_model.assembly_cost(sched, strategy, variant)
            frac = cost_model.roofline_fraction(
                est, t, jax.devices()[0].device_kind)
            row(f"assembly/{name}/{strategy}_{variant}", t * 1e6,
                f"build_us={t_build*1e6:.1f};ne={sched.ne};"
                f"colors={col.num_colors};matches_serial={match[key]};"
                f"roofline_fraction={frac}")
            records.append({
                "mesh": name, "ne": sched.ne, "n": sched.n,
                "k": sched.k, "colors": int(col.num_colors),
                "strategy": strategy, "variant": variant,
                "us": round(t * 1e6, 1),
                "matches_serial": match[key],
                "predicted_ms": round(est.predicted_s * 1e3, 6),
                "bound": est.bound,
                "roofline_fraction": frac,
                "index_dtypes": sched.index_dtypes,
                "build_us": round(t_build * 1e6, 1),
            })
        t_serial = steady_state(
            lambda: scatter_serial(sched, ke), warmup=0, repeats=5,
            name="assembly.serial_oracle", matrix=name)
        row(f"assembly/{name}/serial_numpy", t_serial * 1e6,
            f"ne={sched.ne};oracle=True")
        records.append({"mesh": name, "strategy": "serial",
                        "variant": "numpy",
                        "us": round(t_serial * 1e6, 1),
                        "matches_serial": True})
        # per-mesh summary: does a fused Pallas strategy beat the
        # per-color XLA baseline?  (the CI tet assertion)
        pallas = {k: v for k, v in times.items()
                  if k in ("colored/stream", "colored/onehot",
                           "sorted/stream")}
        best_key = min(pallas, key=pallas.get)
        res = tune_assembly(sched, ke, cache=cache, repeats=3)
        records.append({
            "mesh": name, "summary": True,
            "best_pallas": best_key,
            "best_pallas_us": round(pallas[best_key] * 1e6, 1),
            "percolor_us": round(times["colored/percolor"] * 1e6, 1),
            "pallas_beats_percolor": bool(
                pallas[best_key] < times["colored/percolor"]),
            "speedup_vs_percolor": round(
                times["colored/percolor"] / pallas[best_key], 2),
            "all_match_serial": all(match.values()),
            "tuned": res.key(),
            "tuned_roofline_fraction": res.roofline_fraction.get(
                res.key()),
        })
        row(f"assembly/{name}/summary", pallas[best_key] * 1e6,
            f"best={best_key};speedup_vs_percolor="
            f"{times['colored/percolor'] / pallas[best_key]:.2f};"
            f"tuned={res.key()}")
    # assemble -> tune -> solve (the end-to-end acceptance demo)
    mesh = meshes[0][1]
    sched = assembly_schedule_for(mesh, cache=cache)
    M = values_to_csrc(sched, scatter_colored(
        sched, amesh.poisson_stiffness(mesh, mass=1.0)))
    res = tuner.tune(M, cache=cache)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(M.n)
                    .astype(np.float32))
    t0 = time.perf_counter()
    sol, op = cg_solve(M, b, cache=cache, tol=1e-6, maxiter=2000)
    t_solve = time.perf_counter() - t0
    row("assembly/tri/assemble_tune_solve", t_solve * 1e6,
        f"plan={op.plan.key()};iters={int(sol.iters)};"
        f"converged={bool(sol.converged)}")
    records.append({"mesh": "tri", "pipeline": "assemble_tune_solve",
                    "plan": op.plan.key(), "iters": int(sol.iters),
                    "converged": bool(sol.converged),
                    "solve_us": round(t_solve * 1e6, 1)})
    os.makedirs(os.path.dirname(BENCH_ASSEMBLY_PATH), exist_ok=True)
    with open(BENCH_ASSEMBLY_PATH, "w") as f:
        json.dump({"rows": records}, f, indent=1, sort_keys=True)
    print(f"# assembly: {len(records)} rows -> {BENCH_ASSEMBLY_PATH}")


# ---------------------------------------------------------------------------
# Serving: local vs mesh executors behind the engine (subprocess, 8 devices)
# ---------------------------------------------------------------------------

_SERVING_CODE = """
    import json, time, numpy as np
    from benchmarks.util import steady_state
    from repro.core import csrc, tuner
    from repro.serve import SpmvServingEngine
    OUT = %(out)r
    scale = 4 if %(small)s else 1
    cases = [
        ('fem_band_w16', csrc.fem_band(20000 // scale, 16, seed=2)),
        ('skew_band_w48', csrc.skewed_band(8000 // scale, 48, 3, seed=6)),
    ]
    rng = np.random.default_rng(0)
    cache = tuner.PlanCache()
    rows = []
    # mesh-aware tuning: the per-(matrix, p=8) winner lands in the cache
    # under fingerprint@p8 and drives the mesh engines below
    for name, M in cases:
        res = tuner.tune_mesh(M, 8, cache=cache, repeats=1)
        rows.append({'matrix': name, 'kind': 'mesh_winner',
                     'cache_key': res.fingerprint,
                     'plan': res.plan.key(),
                     'candidates_measured': len(res.timings_s)})
        print(f'serving/{name}/mesh_winner,0.0,plan={res.plan.key()};'
              f'candidates={len(res.timings_s)}')
    for name, M in cases:
        xs = [rng.standard_normal(M.m).astype(np.float32)
              for _ in range(8)]
        for mode, kw in (('local', {}), ('mesh', {'mesh_p': 8})):
            eng = SpmvServingEngine(cache=cache, **kw)
            t0 = time.perf_counter()
            plan = eng.register(name, M)
            t_reg = time.perf_counter() - t0

            def tick():
                for x in xs:
                    eng.submit(name, x)
                return eng.step()

            out = tick()                      # warm the jit caches
            r0 = next(iter(out.values()))
            t_med = steady_state(tick, warmup=0, repeats=5,
                                 name='serve.tick_bench',
                                 matrix=name, mode=mode)
            rows.append({
                'matrix': name, 'executor': r0.executor,
                'plan': plan.key(), 'strategy': plan.strategy,
                'register_us': round(t_reg * 1e6, 1),
                'steady_us_per_tick': round(t_med * 1e6, 1),
                'batched': 8,
            })
            print(f'serving/{name}/{mode},{t_med*1e6:.1f},'
                  f'plan={plan.key()};register_us={t_reg*1e6:.1f};'
                  f'executor={r0.executor}')
    with open(OUT, 'w') as f:
        json.dump({'rows': rows}, f, indent=1, sort_keys=True)
    print(f'# serving: {len(rows)} rows -> {OUT}')
"""


def serving(small: bool):
    """Local vs mesh serving through repro.serve: per-(matrix, p=8)
    mesh-aware tuning, then register (one-time build) vs steady-state
    per-tick latency for an 8-request batch on both executors.  Runs on 8
    forced host devices in a subprocess (device count locks at first jax
    init); rows land in results/BENCH_serving.json and the CI
    serving-smoke job asserts the mesh rows exist."""
    print("# serving: local vs mesh engines (build vs steady-state, "
          "8 shards)")
    os.makedirs(os.path.dirname(BENCH_SERVING_PATH), exist_ok=True)
    print(_cpu_child(_SERVING_CODE % {"out": BENCH_SERVING_PATH,
                                      "small": small}))


# ---------------------------------------------------------------------------
# Local gap: streaming vs one-hot variants under the analytic roofline
# ---------------------------------------------------------------------------

def local_gap(small: bool):
    """The local-path speed gap, closed: per suite matrix and windowed/
    unstructured path, steady-state SpMV and nrhs=8 SpMM of the one-hot
    variant (the PR-5 baseline: (S, W) mask contractions, O(W)/slot)
    against the streaming variant (per-lane gather + segment-sum,
    O(1)/slot), each annotated with the fraction of the analytic roofline
    (roofline/cost_model.py) it achieved.  Also regenerates the
    local-vs-mesh steady-state split: the tuned local engine's per-tick
    latency next to the mesh rows of results/BENCH_serving.json when that
    file exists.  CI bench-smoke asserts, from the written JSON, that the
    streaming variant beats one-hot on the fem_band entry and that every
    plan row carries ``roofline_fraction``."""
    print("# local_gap: streaming vs one-hot variants "
          "(steady-state + roofline fraction)")
    from repro.roofline import cost_model
    scale = 4 if small else 1
    rng = np.random.default_rng(0)
    cases = [
        ("fem_band_w16", csrc.fem_band(20000 // scale, 16, seed=2)),
        ("fem_band_w64_sym", csrc.fem_band(8000 // scale, 64, seed=3,
                                           numeric_symmetric=True)),
        ("skew_band_w48", csrc.skewed_band(8000 // scale, 48, 3, seed=6)),
        ("powerlaw_graph", csrc.powerlaw_laplacian(8000 // scale, seed=7)),
    ]
    records = []
    for name, M in cases:
        stats = tuner.stats_of(M)
        x = jnp.asarray(rng.standard_normal(M.m).astype(np.float32))
        X = jnp.asarray(rng.standard_normal((M.m, 8)).astype(np.float32))
        paths_here = ["kernel", "flat"]
        if paths.nnzsplit_worth_measuring(stats):
            paths_here.append("nnzsplit")
        by_path = {}
        for path in paths_here:
            per_variant = {}
            for variant in ("onehot", "stream"):
                plan = (ExecutionPlan(path="nnzsplit", k_step_sublanes=2,
                                      variant=variant)
                        if path == "nnzsplit"
                        else ExecutionPlan(path=path, tm=128,
                                           variant=variant))
                try:
                    op = ops.SpmvOperator.from_plan(M, plan)
                except ValueError:
                    continue              # window over cap for this grid
                t = time_fn(op, x, warmup=2, repeats=5)
                t_mm = time_fn(op, X, warmup=2, repeats=5)
                est = cost_model.plan_cost(stats, plan)
                frac = cost_model.roofline_fraction(
                    est, t, jax.devices()[0].device_kind)
                per_variant[variant] = {
                    "plan": plan.key(),
                    "spmv_us": round(t * 1e6, 1),
                    "spmm8_us": round(t_mm * 1e6, 1),
                    "predicted_ms": round(est.predicted_s * 1e3, 6),
                    "bound": est.bound,
                    "roofline_fraction": frac,
                }
                row(f"local_gap/{name}/{path}/{variant}", t * 1e6,
                    f"spmm8_us={t_mm * 1e6:.1f};bound={est.bound};"
                    f"roofline_fraction={frac}")
            if {"onehot", "stream"} <= set(per_variant):
                oh, st = per_variant["onehot"], per_variant["stream"]
                by_path[path] = {
                    "variants": per_variant,
                    "stream_speedup_spmv":
                        round(oh["spmv_us"] / st["spmv_us"], 2),
                    "stream_speedup_spmm8":
                        round(oh["spmm8_us"] / st["spmm8_us"], 2),
                }
        if by_path:
            records.append({"matrix": name, "n": M.n, "nnz": M.nnz,
                            "bandwidth": int(stats.bandwidth),
                            "paths": by_path})
    # the local-vs-mesh steady-state split, regenerated with the tuned
    # (variant-aware) local engine; mesh rows join from the serving bench
    # when its JSON is present (that side needs 8 forced devices)
    from repro.serve import SpmvServingEngine
    split = []
    mesh_rows = {}
    if os.path.exists(BENCH_SERVING_PATH):
        for r in json.load(open(BENCH_SERVING_PATH)).get("rows", []):
            if r.get("executor") == "mesh":
                mesh_rows[r["matrix"]] = r.get("steady_us_per_tick")
    for name, M in cases[:2]:
        eng = SpmvServingEngine(autotune=True)
        eng.register(name, M)
        xs = [rng.standard_normal(M.m).astype(np.float32)
              for _ in range(8)]

        def tick():
            for xv in xs:
                eng.submit(name, xv)
            return eng.step()

        tick()                            # warm the jit caches
        t_med = steady_state(tick, warmup=0, repeats=5,
                             name="serve.tick_bench",
                             matrix=name, mode="local")
        local_us = round(t_med * 1e6, 1)
        split.append({"matrix": name, "plan": eng.plan(name).key(),
                      "local_steady_us_per_tick": local_us,
                      "mesh_steady_us_per_tick": mesh_rows.get(name)})
        row(f"local_gap/{name}/local_engine", local_us,
            f"plan={eng.plan(name).key()};"
            f"mesh_us={mesh_rows.get(name)}")
    os.makedirs(os.path.dirname(BENCH_LOCAL_GAP_PATH), exist_ok=True)
    with open(BENCH_LOCAL_GAP_PATH, "w") as f:
        json.dump({"rows": records, "local_vs_mesh": split},
                  f, indent=1, sort_keys=True)
    print(f"# local_gap: {len(records)} rows -> {BENCH_LOCAL_GAP_PATH}")


# ---------------------------------------------------------------------------
# Tuned vs default execution plans (the plan/autotune subsystem)
# ---------------------------------------------------------------------------

def tuned_vs_default(small: bool):
    """Per matrix class: the autotuned ExecutionPlan vs the static default
    (the old hard-coded kernel-else-segment decision) — the paper's point
    that strategy selection is a per-matrix problem, measured."""
    print("# tuned_vs_default: autotuned plan vs static default per class")
    rng = np.random.default_rng(0)
    cache = tuner.PlanCache()          # in-memory; --tune persists to disk
    for name, make in matrices(small):
        M = make()
        x = jnp.asarray(rng.standard_normal(M.m).astype(np.float32))
        default_op = ops.SpmvOperator(M)              # static 'auto'
        result = tuner.tune(M, cache=cache, x=np.asarray(x),
                            candidates=tuner.enumerate_plans(
                                tuner.stats_of(M), colorful_max_n=1200))
        tuned_op = ops.SpmvOperator.from_plan(M, result.plan)
        t_def = time_fn(default_op, x)
        t_tuned = time_fn(tuned_op, x)
        row(f"tuned/{name}", t_tuned * 1e6,
            f"plan={result.plan.key()};default={default_op.plan.key()};"
            f"default_us={t_def*1e6:.1f};speedup={t_def/t_tuned:.2f}")


def pretune(small: bool):
    """Offline pre-tuning (``python -m benchmarks.run --tune``): tune every
    suite matrix and persist the plan cache for solvers/serving to load."""
    cache = tuner.PlanCache(path=PLAN_CACHE_PATH)
    for name, make in matrices(small):
        M = make()
        result = tuner.tune(M, cache=cache)
        state = "cached" if result.cached else "tuned"
        print(f"# pretune {name}: {state} -> {result.plan.key()} "
              f"({result.fingerprint})")
    cache.save()
    print(f"# plan cache written: {PLAN_CACHE_PATH} "
          f"({len(cache)} entries)")


# ---------------------------------------------------------------------------
# §Roofline summary from the dry-run records
# ---------------------------------------------------------------------------

def roofline_summary(small: bool):
    outdir = os.path.join(ROOT, "results", "dryrun")
    if not os.path.isdir(outdir):
        print("# roofline_summary: results/dryrun missing — run "
              "`python -m repro.launch.dryrun` first")
        return
    print("# roofline_summary: single-pod terms per cell (seconds)")
    import glob
    for f in sorted(glob.glob(os.path.join(outdir, "*__16x16.json"))):
        rec = json.load(open(f))
        if rec["status"] != "ok":
            continue
        r = rec["roofline"]
        dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
        row(f"roofline/{rec['arch']}/{rec['shape']}", dom * 1e6,
            f"bottleneck={r['bottleneck']};compute={r['compute_s']:.3e};"
            f"memory={r['memory_s']:.3e};collective={r['collective_s']:.3e};"
            f"useful={r['useful_ratio']:.2f}")


BENCHES = [fig5_sequential, table2_accumulation, fig6_colorful,
           fig89_scaling, schedule_build, coloring_quality, flat_vs_rect,
           nnzsplit_unstructured, assembly, serving, local_gap,
           tuned_vs_default, roofline_summary]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller matrices for CI-speed runs")
    ap.add_argument("--tune", action="store_true",
                    help="pre-tune the suite offline and write "
                         "results/plans.json, then exit")
    ap.add_argument("--only", default=None)
    args, _ = ap.parse_known_args()
    enable_compile_cache()
    if args.tune:
        pretune(args.quick)
        return
    for bench in BENCHES:
        if args.only and args.only not in bench.__name__:
            continue
        bench(args.quick)


if __name__ == "__main__":
    main()
