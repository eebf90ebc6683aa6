"""The program's own spans (``repro.obs``) over the measured window, for
the per-layer metrics that read them.

The program records a span for each layer its calls cross
(``solver.cg_solve`` and its children, ``assembly.value_refresh`` and its
children) in a ring buffer, each with its start on the ``perf_counter``
clock (``t0``) and the counter increments its subtree made (``counts``,
such as ``host_copy_bytes_total`` and ``solver_traces_total``).  A reader
runs after the window, the traced bursts and the check, none of which
follow the window with a solve; set-up solves once or more before it.  So
the window's solves are the last ``len(ctx.cg_iters)`` ``solver.cg_solve``
records, and the window's records are those that start after the end of
the solve before them and no later than the end of the last.

A program that records none of this (spans off, the control, a tree
older than these spans) gives None, and its metrics are left out.
"""
from __future__ import annotations

from typing import Dict, List, Optional

# the program's span prefixes, beside the benchmark's own ``bench.``
PROGRAM_PREFIXES = ("solver.", "tune.", "schedule.", "kernels.",
                    "assembly.", "serve.")
SOLVE = "solver.cg_solve"


def _records() -> List[Dict]:
    from repro import obs
    return [r for r in obs.trace() if "t0" in r and "counts" in r]


def _end(r: Dict) -> float:
    return r["t0"] + r["duration_s"]


def window_spans(ctx) -> Optional[List[Dict]]:
    """The program's records that start inside the window, oldest first,
    or None where the window's solves left none."""
    calls = len(getattr(ctx, "cg_iters", None) or ())
    recs = _records()
    solves = [r for r in recs if r["name"] == SOLVE]
    if not calls or len(solves) < calls:
        return None
    lo = _end(solves[-calls - 1]) if len(solves) > calls else float("-inf")
    hi = _end(solves[-1])
    return [r for r in recs if lo < r["t0"] <= hi]


def seconds_per_call(ctx, name: str) -> Optional[float]:
    """Seconds in spans ``name`` over the window's calls (solves or
    steps), or None where the window recorded none."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    hits = [r["duration_s"] for r in spans if r["name"] == name]
    return sum(hits) / len(ctx.cg_iters) if hits else None


def count_per_call(ctx, counter: str) -> Optional[float]:
    """A counter's increments inside the window's top-level spans over
    the window's calls: the counter's delta over the window a call, since
    the program moves it only under a span."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    return sum(r["counts"].get(counter, 0) for r in spans
               if r["depth"] == 0) / len(ctx.cg_iters)
