"""Host spans and device-reduce records for a traced run.

``install`` wraps a few callables of the program, from outside, in
``jax.profiler.TraceAnnotation``s named ``bench.*`` (they land in the
profiler's trace on the host plane, on the clock of the device planes)
and records the shape of every device reduce. Only a ``--trace 1`` run
installs it; the end-to-end runs leave the program as it is.

  bench.read_shard     TraceStore.read_shard: one shard file read
  bench.scan_prep      aggregation._scan_shard: row mask, group and bin
                       discovery of one (query, shard) slot
  bench.device_reduce  one reducer's device collective, upload of its
                       result included
  bench.tick_exec      QueryService._exec_tick: one tick's execution
  bench.commit         QueryService._commit
  bench.append         run_append inside an ingest tick
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List


class Recorder:
    def __init__(self) -> None:
        self.reduces: List[Dict] = []
        self.spans: List[Dict] = []     # {"name", "t0", "t1"}, monotonic
        self._lock = threading.Lock()
        self._undo: List = []

    def add_reduce(self, rec: Dict) -> None:
        with self._lock:
            self.reduces.append(rec)

    def add_span(self, name: str, t0: float, t1: float) -> None:
        with self._lock:
            self.spans.append({"name": name, "t0": t0, "t1": t1})

    def uninstall(self) -> None:
        for owner, name, orig in reversed(self._undo):
            setattr(owner, name, orig)
        self._undo.clear()


def _span(rec: Recorder, name: str, fn):
    from jax.profiler import TraceAnnotation

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        t0 = time.monotonic()
        try:
            with TraceAnnotation(name):
                return fn(*a, **kw)
        finally:
            rec.add_span(name, t0, time.monotonic())
    return wrapped


def install() -> Recorder:
    from repro.core import aggregation, tracestore
    from repro.core.reducers import BinStats, QuantileSketch
    from repro.serve import query_service
    from jax.profiler import TraceAnnotation

    rec = Recorder()

    def patch(owner, name, new):
        rec._undo.append((owner, name, owner.__dict__[name]))
        setattr(owner, name, new)

    patch(tracestore.TraceStore, "read_shard",
          _span(rec, "bench.read_shard", tracestore.TraceStore.read_shard))
    patch(aggregation, "_scan_shard",
          _span(rec, "bench.scan_prep", aggregation._scan_shard))
    patch(query_service.QueryService, "_exec_tick",
          _span(rec, "bench.tick_exec", query_service.QueryService._exec_tick))
    patch(query_service.QueryService, "_commit",
          _span(rec, "bench.commit", query_service.QueryService._commit))
    patch(query_service, "run_append",
          _span(rec, "bench.append", query_service.run_append))

    for cls in (BinStats, QuantileSketch):
        orig = cls.__dict__["device_reduce"].__func__

        def reduce(klass, seg_ids, values, n_seg, mesh, valid,
                   _orig=orig):
            t0 = time.monotonic()
            with TraceAnnotation("bench.device_reduce"):
                out = _orig(klass, seg_ids, values, n_seg, mesh, valid)
            rec.add_reduce({
                "reducer": klass.name, "t": t0,
                "rows_padded": int(seg_ids.shape[0]),
                "metrics": int(values.shape[0]), "n_seg": int(n_seg),
                "devices": int(mesh.size)})
            return out

        patch(cls, "device_reduce", classmethod(reduce))
    return rec


def start(trace_dir: str) -> Recorder:
    """Install the spans and start the profiler (host spans, device ops,
    no Python function tracing)."""
    import jax
    rec = install()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return rec


def stop(rec: Recorder) -> None:
    import jax
    jax.profiler.stop_trace()
    rec.uninstall()
