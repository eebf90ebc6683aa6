"""Nested spans with two sinks: a ring-buffer trace log and the profiler.

``span(name, **labels)`` is a context manager:

    with obs.span("tune.measure", plan=plan.key()):
        t = measure(op, x)

On exit it appends a record to a bounded ring buffer (``trace()`` reads
it) carrying its ``id``, the enclosing span's ``parent`` name and
``parent_id``, the nesting depth, the start on the wall clock (``start``)
and on the ``perf_counter`` clock (``t0``), the duration, whether the
block raised, and ``counts``: what :func:`tally` added while the span was
open.  Exception-safe: the record is written and the per-thread stack
restored on the error path too, and the exception propagates untouched.

While JAX is loaded, a span also opens a profiler host annotation
(``jax.profiler.TraceAnnotation``) of the same name, with the labels as
its metadata (the event keeps the bare span name), so a profiler trace
shows the program's spans on the host line beside the device's ops.  JAX
is looked up in ``sys.modules`` only, never imported: without JAX there
is no device to trace.

Spans honor the global enable flag: disabled spans skip the clock, the
stack, the ring and the annotation entirely (one attribute read), which
is what keeps the serving hot path inside the <2% overhead budget.
"""
from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import Dict, List, Optional

from .metrics import STATE

DEFAULT_TRACE_CAPACITY = 4096

_trace = collections.deque(maxlen=DEFAULT_TRACE_CAPACITY)
_tls = threading.local()
_ids = itertools.count(1)


def _stack() -> List["Span"]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _annotation(name: str, labels: Dict[str, str]):
    """An entered profiler annotation for this span, or None without
    JAX."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(name, **labels)
    ann.__enter__()
    return ann


class Span:
    """One timed block.  Created via :func:`span`; re-entrant use of a
    single instance is not supported (make a new one per block)."""

    __slots__ = ("name", "labels", "id", "t0", "start", "depth", "parent",
                 "parent_id", "counts", "duration_s", "_live", "_ann")

    def __init__(self, name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = labels
        self.duration_s = None
        self._live = False

    def set_labels(self, **labels):
        """Add labels known only inside the block (a count, a size); the
        record keeps them, the profiler event has those given at entry."""
        self.labels.update({k: str(v) for k, v in labels.items()})

    def __enter__(self) -> "Span":
        if not STATE.enabled:
            return self
        st = _stack()
        up = st[-1] if st else None
        self.id = next(_ids)
        self.depth = len(st)
        self.parent = None if up is None else up.name
        self.parent_id = None if up is None else up.id
        self.counts = {}
        st.append(self)
        self._ann = _annotation(self.name, self.labels)
        self.start = time.time()
        self.t0 = time.perf_counter()
        self._live = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if not self._live:
            return False
        self.duration_s = time.perf_counter() - self.t0
        self._live = False
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        else:                         # unbalanced exit (enable flag moved
            while st and st[-1] is not self:   # mid-span): resync stack
                st.pop()
            if st:
                st.pop()
        _trace.append({
            "name": self.name, "labels": self.labels, "id": self.id,
            "start": self.start, "t0": self.t0,
            "duration_s": self.duration_s,
            "depth": self.depth, "parent": self.parent,
            "parent_id": self.parent_id, "counts": self.counts,
            "ok": exc_type is None,
            "error": (None if exc_type is None
                      else f"{exc_type.__name__}: {exc}"),
        })
        return False                  # never swallow the exception


def span(name: str, **labels) -> Span:
    """A new span context manager (see module docstring).  Label values
    are stringified into the trace record."""
    return Span(name, {k: str(v) for k, v in labels.items()})


def tally(key: str, v: float = 1.0):
    """Add ``v`` to ``counts[key]`` of every span open on this thread, so
    each record says how much its subtree did (``obs.count`` tallies a
    counter's increments under the counter's name)."""
    if not STATE.enabled:
        return
    for sp in _stack():
        sp.counts[key] = sp.counts.get(key, 0) + v


def trace(name: Optional[str] = None) -> List[Dict]:
    """Snapshot of the ring buffer, oldest first; ``name`` filters."""
    recs = list(_trace)
    if name is not None:
        recs = [r for r in recs if r["name"] == name]
    return recs


def clear_trace():
    _trace.clear()


def set_trace_capacity(n: int):
    """Resize the ring buffer (keeps the newest records)."""
    global _trace
    _trace = collections.deque(_trace, maxlen=int(n))
