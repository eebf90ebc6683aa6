"""Performance trajectory: one append-only JSON time series per commit.

``python -m benchmarks.trajectory`` measures a small live point (tune a
representative matrix, serve an 8-request batch to steady state, read the
obs metrics snapshot), folds in every ``results/BENCH_*.json`` summary
already on disk, and appends the point — keyed by git SHA — to
``results/BENCH_trajectory.json``.  The newest point is then diffed
against the previous one: a >25% regression on serving steady-state
per-tick latency or execute p95 fails the run (exit 1) unless
``--warn-only`` (what CI's bench-smoke step uses) or this is the first
point.

Every number in the point flows through the obs spine: plan-cache
hit/miss counters, ``serve_execute_seconds`` quantiles, and the tuner's
predict-vs-measure roofline fractions — so the file doubles as an
integration check that the instrumentation actually fires.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(ROOT, "results")
TRAJECTORY_PATH = os.path.join(RESULTS, "BENCH_trajectory.json")

# >25% worse than the previous point on either metric is a regression
REGRESSION_RATIO = 1.25
GATED_FIELDS = ("steady_us_per_tick", "p95_us")


def _q_us(hist: Dict, q: str) -> Optional[float]:
    v = hist.get(q)
    return None if v is None else round(float(v) * 1e6, 1)


def fold_benches() -> Dict[str, Dict]:
    """Small summary of every results/BENCH_*.json already on disk."""
    import glob
    out: Dict[str, Dict] = {}
    for path in sorted(glob.glob(os.path.join(RESULTS, "BENCH_*.json"))):
        name = os.path.basename(path)[len("BENCH_"):-len(".json")]
        if name == "trajectory":
            continue
        try:
            with open(path) as f:
                d = json.load(f)
        except Exception:
            continue
        rows = d.get("rows", []) if isinstance(d, dict) else []
        summ: Dict[str, object] = {"rows": len(rows)}
        steady = {r["matrix"]: r["steady_us_per_tick"]
                  for r in rows if isinstance(r, dict)
                  and r.get("steady_us_per_tick") is not None
                  and r.get("matrix")}
        if steady:
            summ["steady_us_per_tick"] = steady
        if name == "assembly":
            # per-mesh winner of the fused assembly-scatter bake-off:
            # {mesh: {best_pallas, speedup_vs_percolor, tuned}}
            asm = {r["mesh"]: {
                       "best_pallas": r.get("best_pallas"),
                       "speedup_vs_percolor": r.get("speedup_vs_percolor"),
                       "tuned": r.get("tuned")}
                   for r in rows if isinstance(r, dict) and r.get("summary")
                   and r.get("mesh")}
            if asm:
                summ["assembly"] = asm
        out[name] = summ
    return out


def measure_point(quick: bool = False) -> Dict:
    """Tune + serve one representative matrix and read the metrics."""
    import numpy as np
    from benchmarks.util import steady_state
    from repro import obs
    import jax
    from repro.core import csrc, tuner
    from repro.roofline import cost_model
    from repro.serve import SpmvServingEngine

    n, hb = (2000, 8) if quick else (8000, 16)
    M = csrc.fem_band(n, hb, seed=2)
    cache = tuner.PlanCache()
    snap0 = obs.snapshot()

    res = tuner.tune(M, cache=cache, repeats=2 if quick else 3)
    # per-path achieved-roofline fraction: best measured plan per path,
    # only on a device kind with a peak row (none for the CPU host)
    frac_by_path: Dict[str, float] = {}
    if jax.devices()[0].device_kind in cost_model.DEVICE_PEAKS:
        for key, t in res.timings_s.items():
            pred = res.predictions_s.get(key)
            if not pred or t <= 0:
                continue
            path = key.split(":")[0]
            frac = pred / t
            if frac > frac_by_path.get(path, 0.0):
                frac_by_path[path] = round(frac, 4)

    eng = SpmvServingEngine(cache=cache)
    eng.register("traj", M)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal(M.m).astype(np.float32) for _ in range(8)]

    def tick():
        for x in xs:
            eng.submit("traj", x)
        return eng.step()

    out = tick()                               # warm the jit caches
    r0 = next(iter(out.values()))
    t_tick = steady_state(tick, warmup=0, repeats=3 if quick else 5,
                          name="serve.tick_bench", matrix="traj")

    d = obs.snapshot().diff(snap0)
    exec_h = d.merged_hist("serve_execute_seconds")
    point = {
        "sha": obs.git_sha(),
        "when": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "quick": bool(quick),
        "env": dict(obs.environment_provenance()),
        "serving": {
            "steady_us_per_tick": round(t_tick * 1e6, 1),
            "p50_us": _q_us(exec_h, "p50"),
            "p95_us": _q_us(exec_h, "p95"),
            "p99_us": _q_us(exec_h, "p99"),
            "requests": int(d.total("serve_requests_total")),
            "executor": r0.executor,
        },
        "plan_cache": {
            "hit": int(d.total("plan_cache_lookups_total",
                               kind="plan", outcome="hit")),
            "miss": int(d.total("plan_cache_lookups_total",
                                kind="plan", outcome="miss")),
        },
        "tuner": {
            "enumerated": int(d.total("tuner_candidates_enumerated_total")),
            "pruned": int(d.total("tuner_candidates_pruned_total")),
            "measured": int(d.total("tuner_candidates_measured_total")),
        },
        "roofline_fraction": frac_by_path,
        "winner_plan": res.plan.key(),
        "benches": fold_benches(),
    }
    return point


def load_trajectory(path: str = TRAJECTORY_PATH) -> List[Dict]:
    if not os.path.exists(path):
        return []
    try:
        with open(path) as f:
            d = json.load(f)
        return d.get("points", []) if isinstance(d, dict) else []
    except Exception:
        return []


def append_point(point: Dict, path: str = TRAJECTORY_PATH) -> List[Dict]:
    points = load_trajectory(path)
    points.append(point)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"version": 1, "points": points}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    return points


def gate(points: List[Dict], warn_only: bool = False) -> int:
    """Newest vs previous point on the gated serving fields; returns the
    process exit code (0 ok / 1 regression)."""
    if len(points) < 2:
        print("trajectory: first point, nothing to gate against")
        return 0
    prev, new = points[-2], points[-1]
    failures = []
    for field in GATED_FIELDS:
        a = (prev.get("serving") or {}).get(field)
        b = (new.get("serving") or {}).get(field)
        if a is None or b is None or a <= 0:
            continue
        ratio = b / a
        status = "REGRESSION" if ratio > REGRESSION_RATIO else "ok"
        print(f"trajectory: serving.{field}: {a} -> {b} "
              f"({ratio:.2f}x, {status})")
        if ratio > REGRESSION_RATIO:
            failures.append(field)
    if failures:
        msg = (f"trajectory: >{(REGRESSION_RATIO - 1) * 100:.0f}% "
               f"steady-state regression on: {', '.join(failures)} "
               f"({prev.get('sha', '?')[:12]} -> "
               f"{new.get('sha', '?')[:12]})")
        if warn_only:
            print("WARNING: " + msg)
            return 0
        print("ERROR: " + msg, file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller matrix / fewer repeats (CI smoke)")
    ap.add_argument("--warn-only", action="store_true",
                    help="report regressions without failing")
    ap.add_argument("--out", default=TRAJECTORY_PATH,
                    help="trajectory file (default results/"
                         "BENCH_trajectory.json)")
    args = ap.parse_args(argv)
    point = measure_point(quick=args.quick)
    points = append_point(point, path=args.out)
    print(f"trajectory: point {len(points)} @ {point['sha'][:12]} -> "
          f"{args.out}")
    print(json.dumps({k: point[k] for k in
                      ("serving", "plan_cache", "tuner",
                       "roofline_fraction", "winner_plan")}, indent=1))
    return gate(points, warn_only=args.warn_only)


if __name__ == "__main__":
    raise SystemExit(main())
