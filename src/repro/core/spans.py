"""Named spans at the layer boundaries of the served path.

``with span("repro.shard.read", rows=n): ...`` marks one piece of work:

* while a JAX profiler session runs, the span is a host event of its
  trace (``jax.profiler.TraceAnnotation``), on the clock of the device
  planes, with its stats as event stats;
* always, it adds to in-process totals per span name: the count, the
  total and self time (its duration less that of the spans nested in it
  on the same thread) and the sum of each numeric stat.
  :data:`TOTALS` holds them; ``GET /v1/stats`` serves them as
  ``"spans"``.

Stats known only inside the span are added with ``set`` on the object
the ``with`` statement binds. ``tick`` is an identifier (the spans of
one service tick share it): it rides the profiler event and is left out
of the sums.

This module never imports JAX. The profiler event is written only in a
process that has already imported it, so code that runs before JAX (a
store build, the process backend's workers) stays free of it. A span
costs about a microsecond with no profiler running: keep spans per
tick, shard or dispatch, never per row.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Dict

__all__ = ["span", "SpanTotals", "TOTALS"]

# stats that name a span instead of counting its work
IDENTIFIERS = frozenset({"tick"})


class SpanTotals:
    """Per-name count, total and self time, and stat sums, under one
    lock."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # name -> [count, total_ns, self_ns, {stat: sum}]
        self._by_name: Dict[str, list] = {}

    def add(self, name: str, total_ns: int, self_ns: int,
            stats: Dict) -> None:
        with self._lock:
            rec = self._by_name.get(name)
            if rec is None:
                rec = self._by_name[name] = [0, 0, 0, {}]
            rec[0] += 1
            rec[1] += total_ns
            rec[2] += self_ns
            sums = rec[3]
            for k, v in stats.items():
                if k not in IDENTIFIERS and isinstance(v, (int, float)):
                    sums[k] = sums.get(k, 0) + v

    def snapshot(self) -> Dict[str, Dict]:
        """``{name: {"count", "total_ms", "self_ms", "stats"}}``."""
        with self._lock:
            return {name: {"count": c, "total_ms": t / 1e6,
                           "self_ms": s / 1e6, "stats": dict(sums)}
                    for name, (c, t, s, sums) in
                    sorted(self._by_name.items())}


TOTALS = SpanTotals()
_local = threading.local()
_trace_annotation = None


def _annotation_class():
    global _trace_annotation
    if _trace_annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    return _trace_annotation


class span:
    """Context manager for one span; see the module docstring."""

    __slots__ = ("name", "stats", "_ann", "_parent", "_t0", "_child_ns")

    def __init__(self, name: str, **stats) -> None:
        self.name = name
        self.stats = stats
        self._ann = None

    def set(self, **stats) -> None:
        """Add stats known only once the work is under way."""
        self.stats.update(stats)
        if self._ann is not None:
            self._ann.set_metadata(**stats)

    def __enter__(self) -> "span":
        cls = _annotation_class()
        if cls is not None:
            self._ann = cls(self.name, **self.stats)
            self._ann.__enter__()
        self._parent = getattr(_local, "top", None)
        _local.top = self
        self._child_ns = 0
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter_ns() - self._t0
        _local.top = self._parent
        if self._parent is not None:
            self._parent._child_ns += dur
        if self._ann is not None:
            self._ann.__exit__(*exc)
        TOTALS.add(self.name, dur, dur - self._child_ns, self.stats)
        return False
