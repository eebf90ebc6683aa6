"""Reduction of a JAX profiler trace (``.xplane.pb``) to the device
numbers the benchmark reports.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) whose
``XLA Ops`` line has one event per operation run, and a ``/host:CPU``
plane whose lines hold host spans, the benchmark's own
``TraceAnnotation``s among them.  The two clocks agree to about a
millisecond (on a v5e the device's events read ~1 ms early against the
host spans that launched them), so a window of seconds is cut by its
host span, while a kernel's device time is read from a profiler session
of its own that holds nothing else.

  busy            union of the intervals of a chip's ``XLA Ops`` events
                  inside the traced window, averaged over the chips
  idle gaps       the stretches of the window no operation covers, each
                  labelled with the host span that explains it best: the
                  innermost benchmark annotation or JAX compile/lowering
                  span covering most of it
  top ops         device seconds by operation, control-flow wrappers
                  (``while``, ``conditional``, ``call``) left out since
                  the operations inside them are listed themselves
  device busy     busy seconds of a whole session (a kernel burst)
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
WRAPPERS = ("%while", "%conditional", "%call")
LABEL_HINTS = ("compile", "lower", "TransferToDevice", "DevicePut")
TOP = 10


def idle_percent(ctx):
    """Per cent of the traced window with no device operation running,
    or None where the run was not traced."""
    busy = getattr(ctx, "busy_s", None)
    window = getattr(ctx, "traced_window_s", None)
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)


def union(intervals: Sequence[Tuple[float, float]]):
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def xplane_file(trace_dir) -> str:
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {len(files)}")
    return files[0]


@dataclasses.dataclass
class Trace:
    """The events the reduction needs, in seconds on the host clock."""
    devices: List[List[Tuple[float, float, str, str]]]  # per chip: ops
    host: List[Tuple[float, float, str]]                # every host span

    @classmethod
    def load(cls, trace_dir) -> "Trace":
        import jax
        pd = jax.profiler.ProfileData.from_file(xplane_file(trace_dir))
        devices, host = [], []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                lines = {ln.name: ln for ln in plane.lines}
                mods = sorted((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                              for e in (lines[MODULES_LINE].events
                                        if MODULES_LINE in lines else ()))
                ops = []
                for e in (lines[OPS_LINE].events if OPS_LINE in lines
                          else ()):
                    s, t = e.start_ns * 1e-9, e.end_ns * 1e-9
                    ops.append((s, t, e.name, _module_at(mods, s)))
                devices.append(ops)
            elif plane.name == HOST_PLANE:
                for ln in plane.lines:
                    host.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                                for e in ln.events)
        if not devices:
            raise ValueError("trace holds no TPU device plane")
        return cls(devices=devices, host=host)

    def span(self, name: str) -> Tuple[float, float]:
        hits = [(s, e) for s, e, n in self.host if n == name]
        if len(hits) != 1:
            raise ValueError(f"expected one host span {name!r}, "
                             f"found {len(hits)}")
        return hits[0]

    def busy(self, lo: float, hi: float) -> List[List[Tuple[float, float]]]:
        """Per chip: the merged busy intervals inside [lo, hi]."""
        return [clip(union([(s, e) for s, e, _, _ in ops]), lo, hi)
                for ops in self.devices]


def _module_at(mods, t: float) -> str:
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    if i >= 0 and mods[i][0] <= t <= mods[i][1]:
        return mods[i][2].split("(")[0]
    return "?"


def _op_name(name: str, module: str) -> str:
    """``module:%op = shape`` shortened to the op and its result type."""
    head = name.split("{")[0].strip()
    return f"{module}:{head}"[:120]


def _label(host, lo: float, hi: float, prefixes: Sequence[str]) -> str:
    """The innermost span covering at least half the gap, or the one
    covering most of it, among benchmark annotations and JAX's own
    compile and lowering spans."""
    best, best_key = "host", None
    gap = hi - lo
    for s, e, n in host:
        if e <= lo or s >= hi:
            continue
        if not (n.startswith(prefixes) or any(h in n for h in LABEL_HINTS)):
            continue
        ov = min(e, hi) - max(s, lo)
        key = (ov >= 0.5 * gap, -(e - s) if ov >= 0.5 * gap else ov)
        if best_key is None or key > best_key:
            best, best_key = n, key
    return best


@dataclasses.dataclass
class WindowReduction:
    busy_s: float
    window_s: float
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def reduce_window(trace_dir, labels: Sequence[str] = ("bench.",),
                  window_span: str = WINDOW_SPAN) -> WindowReduction:
    tr = Trace.load(trace_dir)
    lo, hi = tr.span(window_span)
    busy = tr.busy(lo, hi)
    busy_s = sum(covered(b) for b in busy) / len(busy)
    per_op = collections.Counter()
    for ops in tr.devices:
        for s, e, name, mod in ops:
            if name.startswith(WRAPPERS):
                continue
            ov = min(e, hi) - max(s, lo)
            if ov > 0:
                per_op[_op_name(name, mod)] += ov / len(tr.devices)
    gaps = []
    for b in busy[:1]:                 # gaps of the first chip
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(_label(tr.host, s, e, tuple(labels)), e - s)
            for s, e in gaps[:TOP]]
    return WindowReduction(busy_s=busy_s, window_s=hi - lo,
                           top_ops=[[n, v] for n, v in
                                    per_op.most_common(TOP)],
                           idle_gaps=[[n, v] for n, v in idle])


def device_busy_s(trace_dir) -> float:
    """Busy seconds of the first chip over the whole session."""
    tr = Trace.load(trace_dir)
    return covered(tr.busy(float("-inf"), float("inf"))[0])
