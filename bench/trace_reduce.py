"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers,
and the table of peaks they are measured against.

A trace is read with ``jax.profiler.ProfileData``. Device operations
are the events of the ``XLA Ops`` line of each ``/device:...`` plane;
device programs are the events of its ``XLA Modules`` line. A trace
taken on the CPU has no device plane: there the operations are the host
events that carry an ``hlo_op`` stat, counted as device
``device_ordinal``. Host spans are the ``bench.*`` events that the
benchmark's own ``TraceAnnotation``s write on the host plane.

All times are nanoseconds on the trace's clock; ``reduce`` clips every
interval to the window ``[w0, w1)`` given by the ``bench.window`` span.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

# Peaks of one chip, by ``device_kind`` as JAX reports it.
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "ici_bits_per_s": 1600e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}

COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# spans that hold others: a gap goes to one of them only when no inner
# span overlaps it
OUTER_SPANS = ("bench.tick_exec",)


def peaks_for(device_kind: str) -> Dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"it to bench/trace_reduce.py with its source"
                       ) from None


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


# --- intervals ----------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], w0: float, w1: float
         ) -> List[Interval]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if min(e, w1) > max(s, w0)]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` not in ``b``."""
    b = union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], w0: float, w1: float) -> List[Interval]:
    return subtract([(w0, w1)], busy)


# --- reading the planes -------------------------------------------------------

def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:              # noqa: BLE001 — a stat jax cannot read
        return {}


def op_name(name: str) -> str:
    """A device op's name without its HLO text: ``%fusion.3 = f32[3,128]
    {...} fusion(...)`` reads ``fusion.3 f32[3,128]``."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name.lstrip("%")
    return f"{lhs.lstrip('%')} {rhs.split(' ')[0].split('{')[0]}"


def device_events(pd) -> Dict[int, Dict[str, List[Tuple]]]:
    """``{device: {"ops": [(start, end, name)], "modules": [...]}}``."""
    out: Dict[int, Dict[str, List[Tuple]]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        try:
            dev = int(plane.name.rsplit(":", 1)[1])
        except ValueError:
            continue
        d = out.setdefault(dev, {"ops": [], "modules": []})
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                line.name)
            if key is None:
                continue
            for ev in line.events:
                name = op_name(ev.name) if key == "ops" else ev.name
                d[key].append((ev.start_ns, ev.end_ns, name))
    if out:
        return out
    for plane in pd.planes:                 # a CPU trace
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                st = _stats(ev)
                if "hlo_op" not in st:
                    continue
                dev = int(st.get("device_ordinal", 0))
                d = out.setdefault(dev, {"ops": [], "modules": []})
                d["ops"].append((ev.start_ns, ev.end_ns, op_name(ev.name)))
                d["modules"].append((ev.start_ns, ev.end_ns,
                                     str(st.get("hlo_module", ev.name))))
    return out


def host_spans(pd, prefix: str = SPAN_PREFIX
               ) -> List[Tuple[float, float, str, str]]:
    """``(start, end, name, thread)`` of every host span named
    ``prefix...``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        # one line per thread; threads may share a line name
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}/{line.name}"
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.start_ns, ev.end_ns, ev.name, thread))
    return out


def self_times(spans: Sequence[Tuple[float, float, str, str]],
               w0: float, w1: float) -> Dict[str, float]:
    """Self time of each span name inside the window: its duration less
    the spans directly nested in it on the same thread (spans of one
    thread nest as a stack)."""
    by_thread = collections.defaultdict(list)
    for s in spans:
        by_thread[s[3]].append(s)
    out: Dict[str, float] = collections.defaultdict(float)

    def inside(s, e) -> float:
        return max(0.0, min(e, w1) - max(s, w0))

    for items in by_thread.values():
        items.sort(key=lambda t: (t[0], -t[1]))
        stack: List[List] = []          # [end, name, own time]
        for s, e, name, _ in items:
            while stack and stack[-1][0] <= s:
                _, n, own = stack.pop()
                out[n] += own
            if stack:
                stack[-1][2] -= inside(s, e)
            stack.append([e, name, inside(s, e)])
        for _, n, own in stack:
            out[n] += own
    return dict(out)


# --- the reduction ------------------------------------------------------------

def reduce(pd, w0: Optional[float] = None, w1: Optional[float] = None,
           span_names: Sequence[str] = ()) -> Dict:
    """Busy and idle time of every device over the window, device time
    by program and by operation, exposed collective time, host self
    time by span, and the longest idle gaps by the host span in them.

    Without ``w0``/``w1`` the window is the ``bench.window`` span."""
    spans = host_spans(pd)
    if w0 is None or w1 is None:
        win = [s for s in spans if s[2] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        w0, w1 = win[-1][0], win[-1][1]
    devs = device_events(pd)
    window_ns = float(w1 - w0)
    per_dev = {}
    by_module: Dict[str, float] = collections.defaultdict(float)
    by_op: Dict[str, float] = collections.defaultdict(float)
    exposed_coll = 0.0
    for dev, d in sorted(devs.items()):
        ops = clip([(s, e) for s, e, _ in d["ops"]], w0, w1)
        busy = union(ops)
        per_dev[dev] = {"busy_ns": total(busy), "busy": busy}
        for s, e, name in d["ops"]:
            c = clip([(s, e)], w0, w1)
            if c:
                by_op[name] += total(c)
        for s, e, name in d["modules"]:
            c = clip([(s, e)], w0, w1)
            if c:
                by_module[name] += total(c)
        coll = union(clip([(s, e) for s, e, n in d["ops"]
                           if n.startswith(COLLECTIVE_PREFIXES)], w0, w1))
        compute = [(s, e) for s, e, n in d["ops"]
                   if not n.startswith(COLLECTIVE_PREFIXES)]
        exposed_coll += total(subtract(coll, compute))
    n_dev = max(len(per_dev), 1)
    busy_ns = sum(v["busy_ns"] for v in per_dev.values()) / n_dev
    attributed = [s for s in spans if s[2] != WINDOW_SPAN
                  and (not span_names or s[2] in span_names)]
    idle = []
    if per_dev:
        first = per_dev[min(per_dev)]["busy"]
        longest = sorted(gaps(first, w0, w1), key=lambda g: g[0] - g[1])
        for s, e in longest[:10]:
            cover: Dict[str, float] = collections.defaultdict(float)
            for a, b, name, _ in attributed:
                ov = min(b, e) - max(a, s)
                if ov > 0:
                    cover[name] += ov
            inner = {k: v for k, v in cover.items() if k not in OUTER_SPANS}
            pick = inner or cover
            who = max(pick, key=pick.get) if pick else "no_bench_span"
            idle.append((who, (e - s) / 1e9))
    return {
        "window_ns": window_ns,
        "n_devices": len(per_dev),
        "busy_ns": busy_ns,
        "busy_ns_by_device": {k: v["busy_ns"] for k, v in per_dev.items()},
        "by_module_ns": dict(by_module),
        "by_op_ns": dict(by_op),
        "exposed_collective_ns": exposed_coll,
        "self_ns": self_times([s for s in spans if s[2] != WINDOW_SPAN],
                              w0, w1),
        "idle_gaps": idle,
    }


def breakdown(red: Dict, n: int = 10) -> Dict[str, List]:
    """The result line's ``breakdown``: the device operations that took
    most time and the longest idle gaps, in seconds."""
    ops = sorted(red["by_op_ns"].items(), key=lambda t: -t[1])[:n]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red["idle_gaps"][:n]]}
