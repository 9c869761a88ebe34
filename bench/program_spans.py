"""The program's own spans in a traced run, and the idle gaps they name.

The program writes ``repro.*`` host spans (``repro.core.spans``) into
the profiler's trace, with their stats as event stats. This module reads
them, with JAX's own compile events under the one label ``jax.compile``,
from the run's ``.xplane.pb``:

* per name, the self time inside the window (``trace_reduce.self_times``
  over these spans alone, so a compile inside a span is its child), and
  the count, total duration and stat sums of the events that start in
  the window; the events themselves, for readers that filter on a stat;
* the first device's ten longest idle gaps, each named for what the host
  was doing: the span that covers most of the gap where it is the
  innermost of ``jax.compile`` and the ``repro.*`` spans that are not
  outer (``OUTER``), and otherwise ``trace_reduce``'s rule over the
  ``bench.*`` spans. They are written on standard error.

The metric readers call ``program(ctx)``. It returns None where there is
nothing to read: a run without the profiler, or a program that writes no
``repro.*`` span. ``bench.*`` spans and every key of
``trace_reduce.reduce`` are left as they are.
"""

from __future__ import annotations

import collections
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import costs
import trace_reduce as tr

PREFIX = "repro."
COMPILE = "jax.compile"
# JAX's host events for a compile: lowering to HLO, and the backend's
# compile (absent when the executable comes from the persistent cache)
COMPILE_EVENTS = ("lower_sharding_computation", "backend_compile_and_load")
# spans that hold the others: they name a gap only through the bench rule
OUTER = ("repro.tick.exec", "repro.tick.lanes")

Span = Tuple[float, float, str, str]         # start, end, name, thread

_memo: Dict[Tuple[str, int], Optional[Dict]] = {}


def trace_dir(ctx) -> Optional[str]:
    """The run's trace directory: ``ctx.trace_dir`` where the harness
    sets it, else the ``trace_dir`` argument of ``run.finish``, which
    calls the readers."""
    d = getattr(ctx, "trace_dir", None)
    f = sys._getframe(1)
    while d is None and f is not None:
        if f.f_code.co_name == "finish":
            d = f.f_locals.get("trace_dir")
        f = f.f_back
    return d


def program(ctx) -> Optional[Dict]:
    if getattr(ctx, "trace", None) is None:
        return None
    d = trace_dir(ctx)
    if d is None:
        return None
    try:
        path = tr.find_xplane(d)
    except FileNotFoundError:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _memo:
        red = reduce(tr.load(path))
        if red is not None:
            print("idle gaps by program span: " + "; ".join(
                f"{who} {secs:.3f} s" for who, secs in red["idle_gaps"]),
                file=sys.stderr, flush=True)
        _memo[key] = red
    return _memo[key]


# --- reading the trace -------------------------------------------------------

def host_events(pd) -> Tuple[List[Span], List[Dict], List[Span]]:
    """Program spans (``repro.*`` and ``jax.compile``) with their stats,
    and the ``bench.*`` spans, as ``(start, end, name, thread)``."""
    spans, stats, bench = [], [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}/{line.name}"
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, name, thread))
                    stats.append(tr._stats(ev))
                elif name in COMPILE_EVENTS:
                    spans.append((ev.start_ns, ev.end_ns, COMPILE, thread))
                    stats.append({})
                elif name.startswith(tr.SPAN_PREFIX):
                    bench.append((ev.start_ns, ev.end_ns, name, thread))
    return spans, stats, bench


def reduce(pd, w0: Optional[float] = None, w1: Optional[float] = None
           ) -> Optional[Dict]:
    """The program's spans over the window (the ``bench.window`` span
    unless given); None when the trace holds no ``repro.*`` span."""
    spans, stats, bench = host_events(pd)
    if not any(s[2].startswith(PREFIX) for s in spans):
        return None
    if w0 is None or w1 is None:
        win = [s for s in bench if s[2] == tr.WINDOW_SPAN]
        if not win:
            raise ValueError(f"no {tr.WINDOW_SPAN!r} span in the trace")
        w0, w1 = win[-1][0], win[-1][1]
    names: Dict[str, Dict] = {}
    events: Dict[str, List[Tuple[float, float, Dict]]] = \
        collections.defaultdict(list)
    for (s, e, name, _), st in zip(spans, stats):
        if not w0 <= s < w1:
            continue
        rec = names.setdefault(name, {"count": 0, "total_ns": 0.0,
                                      "self_ns": 0.0, "stats": {}})
        rec["count"] += 1
        rec["total_ns"] += e - s
        for k, v in st.items():
            if isinstance(v, (int, float)) and k != "tick":
                rec["stats"][k] = rec["stats"].get(k, 0) + v
        events[name].append((s, e, st))
    for name, ns in tr.self_times(spans, w0, w1).items():
        names.setdefault(name, {"count": 0, "total_ns": 0.0,
                                "self_ns": 0.0, "stats": {}})
        names[name]["self_ns"] = ns
    bench = [s for s in bench if s[2] != tr.WINDOW_SPAN]
    return {"w0": w0, "w1": w1, "names": names, "events": dict(events),
            "idle_gaps": name_gaps(longest_gaps(pd, w0, w1), spans, bench)}


def longest_gaps(pd, w0: float, w1: float, n: int = 10
                 ) -> List[Tuple[float, float]]:
    """The first device's ``n`` longest idle gaps in the window."""
    devs = tr.device_events(pd)
    if not devs:
        return []
    ops = devs[min(devs)]["ops"]
    busy = tr.union(tr.clip([(s, e) for s, e, _ in ops], w0, w1))
    return sorted(tr.gaps(busy, w0, w1), key=lambda g: g[0] - g[1])[:n]


# --- naming the idle gaps ----------------------------------------------------

def innermost(spans: Sequence[Span]) -> List[Span]:
    """Each span's own intervals: its interval less those of the spans
    nested in it on the same thread (spans of one thread nest as a
    stack). Every instant of a thread lies in the own interval of its
    innermost span only."""
    by_thread = collections.defaultdict(list)
    for s in spans:
        by_thread[s[3]].append(s)
    out: List[Span] = []
    for items in by_thread.values():
        items.sort(key=lambda t: (t[0], -t[1]))
        stack: List[List] = []              # [span, children]
        for sp in items + [None]:
            while stack and (sp is None or stack[-1][0][1] <= sp[0]):
                (s, e, name, th), kids = stack.pop()
                for a, b in tr.subtract([(s, e)], kids):
                    out.append((a, b, name, th))
            if sp is not None:
                if stack:
                    stack[-1][1].append((sp[0], sp[1]))
                stack.append([sp, []])
    return out


def name_gaps(gaps: Sequence[Tuple[float, float]], spans: Sequence[Span],
              bench: Sequence[Span]) -> List[Tuple[str, float]]:
    """``(name, seconds)`` of each gap: the innermost program span (not
    an outer one) that covers most of it, else the ``bench.*`` span
    ``trace_reduce.reduce`` would name it for, else ``no_bench_span``."""
    own = innermost([s for s in spans if s[2] not in OUTER])
    out = []
    for g0, g1 in gaps:
        who = _most(own, g0, g1, ())
        if who is None:
            who = _most(bench, g0, g1, tr.OUTER_SPANS) or "no_bench_span"
        out.append((who, (g1 - g0) / 1e9))
    return out


def _most(spans: Sequence[Span], g0: float, g1: float,
          outer: Sequence[str]) -> Optional[str]:
    cover: Dict[str, float] = collections.defaultdict(float)
    for a, b, name, _ in spans:
        ov = min(b, g1) - max(a, g0)
        if ov > 0:
            cover[name] += ov
    inner = {k: v for k, v in cover.items() if k not in outer}
    pick = inner or cover
    return max(pick, key=pick.get) if pick else None


# --- what the readers share --------------------------------------------------

def self_ms(red: Dict, *names: str) -> float:
    return sum(red["names"].get(n, {}).get("self_ns", 0.0)
               for n in names) / 1e6


def duration_ms(red: Dict, name: str, **where) -> float:
    """Total duration of the ``name`` events that start in the window,
    those whose stats match ``where`` only."""
    return sum(e - s for s, e, st in red["events"].get(name, ())
               if all(st.get(k) == v for k, v in where.items())) / 1e6


def ticks(red: Dict, kind: str) -> List[Dict]:
    """Stats of the ``kind`` ticks that started executing in the
    window."""
    return [st for _, _, st in red["events"].get("repro.tick.exec", ())
            if st.get("kind") == kind]


def cold_queries(ctx) -> int:
    """Queries of the window that no cache answered (``host_prep_ms.cold``
    counts per such query)."""
    return sum(1 for r in ctx.done
               if not r["cache_hit"] and not r["inflight_hit"])


def per_cold_query(ctx, fn) -> Optional[float]:
    red = program(ctx)
    n = cold_queries(ctx)
    return fn(red) / n if red is not None and n else None


def per_ingest_tick(ctx, fn) -> Optional[float]:
    red = program(ctx)
    if red is None:
        return None
    n = len(ticks(red, "ingest"))
    return fn(red) / n if n else None


def roofline(ctx, reducer: str, program_name: str) -> Optional[float]:
    """Share of the HBM roofline, in %, of one reducer's device program:
    the bytes its calls that start in the window must move (the shapes
    each ``repro.reduce.dispatch`` span carries, ``costs.reduce_bytes``)
    over the window's device time of the programs named
    ``program_name``, times the chip's HBM bandwidth."""
    red = program(ctx)
    if red is None:
        return None
    calls = [st for _, _, st in red["events"].get("repro.reduce.dispatch",
                                                  ())
             if st.get("reducer") == reducer]
    dev_ns = sum(v for k, v in ctx.trace["by_module_ns"].items()
                 if program_name in k)
    if not calls or dev_ns <= 0:
        return None
    moved = sum(costs.reduce_bytes(st) for st in calls)
    bw = tr.peaks_for(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * moved / (dev_ns / 1e9 * bw)
