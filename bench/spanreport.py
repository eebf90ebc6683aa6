#!/usr/bin/env python3
"""Run one benchmark cell as ``bench/run.py`` does, then print the
program's spans over its window, one line a span name:

    python3 bench/spanreport.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Each line gives a span's seconds a call of the window (a solve or a
step) in all (``total``) and outside its child spans (``self``), and how
many such spans a call opens.  The result line is printed on standard
output as ``run.py`` prints it; the table follows on standard error.  A
program that records no such spans prints no table.
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import harness  # noqa: E402
import programspans  # noqa: E402


def table(ctx):
    """``[(name, total_s, self_s, n)]`` a call of the window, the longest
    first; empty where the window recorded no program spans."""
    spans = programspans.window_spans(ctx)
    if not spans:
        return []
    calls = len(ctx.cg_iters)
    inner = {}
    for r in spans:
        if r["parent_id"] is not None:
            inner[r["parent_id"]] = (inner.get(r["parent_id"], 0.0)
                                     + r["duration_s"])
    agg = {}
    for r in spans:
        t, s, n = agg.get(r["name"], (0.0, 0.0, 0))
        agg[r["name"]] = (t + r["duration_s"],
                          s + r["duration_s"] - inner.get(r["id"], 0.0),
                          n + 1)
    return sorted(((name, t / calls, s / calls, n / calls)
                   for name, (t, s, n) in agg.items()),
                  key=lambda row: -row[1])


def main() -> int:
    seen = {}
    read = harness.read_metrics

    def keep(entries, ctx, bench=harness.BENCH):
        seen["ctx"] = ctx
        return read(entries, ctx, bench)
    harness.read_metrics = keep
    rc = harness.main(t_start=T_START)
    if "ctx" in seen:
        for name, total, own, n in table(seen["ctx"]):
            print(f"span {name:28s} total_s={total:.6f} self_s={own:.6f} "
                  f"n={n:.2f}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
