#!/usr/bin/env python3
"""Readings for setting a cell's limits: many seeds in one process, so
that set-up is paid once.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 8
        [--control]

The first seed goes through the benchmark's own run (``harness.run_cell``)
and prints its result line, ``correct`` with it.  Every further seed makes
the seed's data, runs a window of ``--seconds`` at the cell's own load and
the check, and prints one JSON line with every number compared and the
end-to-end metrics.  ``--control`` puts the bf16 reference in the
program's place (control.py) for all of them.  The cell's configuration,
traffic and limits are its own files, as the benchmark reads them, and
like the benchmark it refuses to run without the chips the cell asks for.
"""
import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    cell = harness.find_cell(harness.load_spec(), args.workload)
    try:
        harness.check_chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    entries = None
    if args.control:
        import control
        entries = control.entries(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    first = harness.run_cell(cell, seeds[0], args.seconds, False, T_START,
                             entries=entries)
    print(json.dumps(dict(first, seed=seeds[0], control=args.control)),
          flush=True)
    if len(seeds) == 1:
        return 0
    runner = cell.runner.Runner(cell, harness.CACHE / "plans" / cell.name,
                                entries=entries)
    runner.setup_static()
    for seed in seeds[1:]:
        runner.setup_seed(seed)
        runner.window(args.seconds)
        ctx = SimpleNamespace(setup_s=0.0)
        runner.fill(ctx)
        runner.collect()
        checks, attempted, failed = runner.check()
        print(json.dumps({
            "seed": seed, "control": args.control, "attempted": attempted,
            "failed": failed, "checks": {n: v for n, v, _ in checks},
            "window": runner.window_summary(),
            "metrics": harness.read_metrics(cell.end_to_end[1:], ctx)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
