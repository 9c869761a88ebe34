"""Plain float64 reference of a served query, and the comparison.

The reference starts from the capture itself (each rank's kernel and
memcpy arrays, made from the seed) and imports nothing of the program.
It restates, in plain numpy, what a query means:

* the store's rows are the left join of kernels with the memcpys on the
  same device that start within ``[k_start - w, k_end + w]``, the first
  ``cap`` of them in start order; a kernel with no such memcpy gives one
  row with the memcpy columns zeroed (``m_kind`` -1). Each generation
  rank joins only the kernels and memcpys that start inside its own
  block of whole time bins. A row with a memcpy on another device stays
  a row, with the memcpy columns zeroed;
* event times pass through float64 on their way out of SQLite, as the
  program's reader takes them;
* bins are whole ``interval_ns`` steps from the first kernel's start;
* a query masks rows (half-open window on ``k_start``, rank subset,
  transfer-kind subset), groups them by the ``group_by`` column, and
  reports per group and metric the count, mean, min and max, and for a
  ``"p99"`` query the number of time bins whose 99th percentile of the
  first metric lies above the Tukey fence ``Q3 + 1.5 IQR`` of the
  occupied bins.

``compare`` sets a served answer beside the reference and returns the
numbers that decide ``correct``; ``LIMITS`` holds the limit of each.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# Unit roundoff of float32. A device sum of n non-negative float32
# terms, in any order, is within (n + 2) * U32 of the exact sum
# (n - 1 roundings of partial sums, one rounding of each input to
# float32, one of the result); every metric served here is >= 0.
U32 = 2.0 ** -24
# The quantile sketch estimates an order statistic by the geometric
# midpoint of its log2 bucket (8 per octave), so within 2**(1/16) - 1
# of the value; values below 1.0 count as 1.0. The slack covers the
# float32 rounding of each value before it is bucketed.
SKETCH_REL = 2.0 ** (1.0 / 16.0) - 1.0 + 1e-5
SKETCH_FLOOR = 1.0
P99 = 0.99
FENCE_K = 1.5

# The numbers compared, each with its limit (PERF.md gives the readings
# each limit was set from). All but mean_gap are exact comparisons.
LIMITS = {
    "unanswered": 0,        # sampled queries with no answer
    "count_mismatch": 0,    # rows, groups and per-cell counts that differ
    "minmax_mismatch": 0,   # min/max cells not equal in float32
    "fence_outside": 0,     # p99 fences outside the sketch's reach
    "mean_gap": 1.0,        # worst mean error, in float32 sum bounds
}

COLUMNS = ("k_start", "bin", "k_device", "k_name", "k_stall", "m_bytes",
           "m_kind", "m_duration", "src_rank")


def _as_read(x) -> np.ndarray:
    """An int64 time as the program reads it: through float64."""
    return np.asarray(x, np.int64).astype(np.float64).astype(np.int64)


# --- the joined table ---------------------------------------------------------

def join_rows(kern: Dict[str, np.ndarray], mem: Dict[str, np.ndarray],
              rank: int, window_ns: int, cap: int) -> Dict[str, np.ndarray]:
    """One rank DB's slice of kernels joined with its memcpys.

    ``kern``/``mem`` hold ``start``, ``end``, ``device`` (and
    ``memory_stall``, ``name_id`` / ``bytes``, ``copy_kind``) in the
    order they were written; memcpys were written in start order."""
    ks, ke = _as_read(kern["start"]), _as_read(kern["end"])
    ms, me = _as_read(mem["start"]), _as_read(mem["end"])
    order = np.argsort(ms, kind="stable")
    ms, me = ms[order], me[order]
    m_dev = np.asarray(mem["device"])[order]
    m_bytes = np.asarray(mem["bytes"])[order]
    m_kind = np.asarray(mem["copy_kind"])[order]
    first = np.searchsorted(ms, ks - window_ns, side="left")
    last = np.searchsorted(ms, ke + window_ns, side="right")
    n_match = np.minimum(last - first, cap)
    n_rows = np.maximum(n_match, 1)
    k_of_row = np.repeat(np.arange(len(ks)), n_rows)
    row_start = np.cumsum(n_rows) - n_rows
    slot = np.arange(int(n_rows.sum())) - row_start[k_of_row]
    m_of_row = first[k_of_row] + slot
    has = slot < n_match[k_of_row]
    m_safe = np.where(has, m_of_row, 0)
    k_dev = np.asarray(kern["device"])[k_of_row]
    if len(ms):
        has &= m_dev[m_safe] == k_dev
    else:
        has[:] = False

    def mcol(arr, null):
        if not len(ms):
            return np.full(len(k_of_row), null, np.float64)
        return np.where(has, np.asarray(arr, np.float64)[m_safe], null)

    return {
        "k_start": ks[k_of_row],
        "k_device": k_dev.astype(np.float64),
        "k_name": np.asarray(kern["name_id"], np.float64)[k_of_row],
        "k_stall": np.asarray(kern["memory_stall"],
                              np.float32).astype(np.float64)[k_of_row],
        "m_bytes": mcol(m_bytes, 0.0),
        "m_kind": mcol(m_kind, -1.0),
        "m_duration": mcol(me, 0.0) - mcol(ms, 0.0),
        "src_rank": np.full(len(k_of_row), float(rank)),
    }


def _trace_columns(tr) -> Tuple[Dict, Dict]:
    k, m = tr.kernels, tr.memcpys
    kern = {"start": k.start, "end": k.end, "device": k.device,
            "memory_stall": k.memory_stall, "name_id": k.name_id}
    mem = {"start": m.start, "end": m.end, "device": m.device,
           "bytes": m.bytes, "copy_kind": m.copy_kind}
    return kern, mem


def _select(cols: Dict[str, np.ndarray], keep: np.ndarray) -> Dict:
    return {c: np.asarray(v)[keep] for c, v in cols.items()}


class Plan:
    """Time bins: ``n`` steps of ``interval`` ns from ``t_start``."""

    def __init__(self, t_start: int, t_end_max: int, interval: int):
        self.t_start = int(t_start)
        self.interval = int(interval)
        self.n = max(1, -(-(int(t_end_max) - self.t_start) // self.interval))

    def edge(self, i: int) -> int:
        return self.t_start + i * self.interval

    def bin_of(self, ts: np.ndarray) -> np.ndarray:
        return np.clip((ts - self.t_start) // self.interval, 0, self.n - 1)

    def rank_blocks(self, n_ranks: int) -> List[Tuple[int, int]]:
        """Each generation rank's ``[lo, hi)``: contiguous blocks of
        whole bins, the first ``n % P`` ranks one bin longer."""
        sizes = [self.n // n_ranks + (1 if r < self.n % n_ranks else 0)
                 for r in range(n_ranks)]
        out, b = [], 0
        for s in sizes:
            out.append((self.edge(b), self.edge(b + s)))
            b += s
        return out


def build_table(traces: Sequence, interval_ns: int, window_ns: int,
                cap: int, n_workers: int) -> Tuple[Dict, Plan]:
    """The store's rows for a whole capture, as ``n_workers`` generation
    ranks build it. Returns (columns sorted by k_start, plan)."""
    t0 = min(int(np.min(tr.kernels.start)) for tr in traces)
    t1 = max(int(np.max(tr.kernels.end)) for tr in traces)
    plan = Plan(t0, t1, interval_ns)
    parts = []
    for lo, hi in plan.rank_blocks(n_workers):
        if hi <= lo:
            continue
        for tr in traces:
            kern, mem = _trace_columns(tr)
            kin = (kern["start"] >= lo) & (kern["start"] < hi)
            min_ = (mem["start"] >= lo) & (mem["start"] < hi)
            if kin.any():
                parts.append(join_rows(_select(kern, kin),
                                       _select(mem, min_), tr.rank,
                                       window_ns, cap))
    return finish_table(parts, plan), plan


def finish_table(parts: List[Dict], plan: Plan) -> Dict[str, np.ndarray]:
    cols = {c: np.concatenate([p[c] for p in parts])
            for c in parts[0] if c != "bin"}
    order = np.argsort(cols["k_start"], kind="stable")
    cols = {c: v[order] for c, v in cols.items()}
    cols["bin"] = plan.bin_of(cols["k_start"])
    return cols


# --- one query ----------------------------------------------------------------

def _rows(table: Dict[str, np.ndarray], spec: Dict) -> np.ndarray:
    """Indices of the rows a query keeps (``k_start`` is sorted)."""
    ts = table["k_start"]
    lo, hi = 0, len(ts)
    if spec.get("time_window") is not None:
        t0, t1 = (int(x) for x in spec["time_window"])
        lo, hi = np.searchsorted(ts, [t0, t1], side="left")
    keep = np.ones(hi - lo, bool)
    for col, key in (("src_rank", "ranks"), ("k_name", "kernel_names"),
                     ("m_kind", "transfer_kinds")):
        if spec.get(key) is not None:
            keep &= np.isin(table[col][lo:hi],
                            np.asarray(spec[key], np.float64))
    return lo + np.flatnonzero(keep)


def _round(values: np.ndarray, precision: str) -> np.ndarray:
    """Values as a run in ``precision`` would hold them."""
    if precision == "float64":
        return values
    if precision == "bfloat16":
        import ml_dtypes
        return values.astype(np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def answer(table: Dict[str, np.ndarray], plan: Plan, spec: Dict,
           precision: str = "float64") -> Dict:
    """The reference's answer to one query spec, with what the
    comparison needs beside it: per group and metric the largest count
    in one time bin (``nmax``), and for a fenced query the range of
    anomalous-bin counts that the quantile sketch can give."""
    keep = _rows(table, spec)
    metrics = list(spec["metrics"])
    group_by = spec.get("group_by")
    bins = table["bin"][keep]
    if group_by is None:
        gkey = np.zeros(len(keep))
    else:
        gkey = table[group_by][keep]
    keys, gid = np.unique(gkey, return_inverse=True)
    if len(keys) == 0:
        keys = np.zeros(1)
    groups: Dict[str, Dict] = {}
    for gi, key in enumerate(keys):
        sel = gid == gi
        nmax = (int(np.bincount(bins[sel]).max()) if sel.any() else 0)
        per = {}
        for m in metrics:
            v = _round(table[m][keep][sel], precision)
            n = int(v.size)
            per[m] = {"count": n,
                      "mean": float(v.sum() / n) if n else 0.0,
                      "min": float(v.min()) if n else 0.0,
                      "max": float(v.max()) if n else 0.0,
                      "nmax": nmax}
        groups[f"{float(key):g}"] = per
    out = {"n_samples": int(len(keep)), "n_bins": int(plan.n),
           "group_by": group_by, "groups": groups}
    if spec.get("anomaly_score", "mean") == "p99":
        v = _round(table[metrics[0]][keep], precision)
        out["fence_range"], out["anomalous_bins"] = fence_range(
            bins, v, plan.n)
    return out


def p99_by_bin(bins: np.ndarray, values: np.ndarray, n_bins: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact type-1 99th percentile of each bin (0 for an empty bin)."""
    order = np.lexsort((values, bins))
    b, v = bins[order], values[order]
    counts = np.bincount(b, minlength=n_bins)
    first = np.cumsum(counts) - counts
    rank = np.maximum(np.ceil(P99 * counts.astype(np.float64)), 1)
    pick = first + rank.astype(np.int64) - 1
    out = np.zeros(n_bins)
    occ = counts > 0
    out[occ] = v[pick[occ]]
    return out, occ


def fence_range(bins: np.ndarray, values: np.ndarray, n_bins: int
                ) -> Tuple[Tuple[int, int], int]:
    """Fewest and most bins a p99 fence can flag when each bin's score
    is the sketch's estimate of its p99, and the count the exact p99
    itself gives. The estimate lies within SKETCH_REL of max(p99, 1), so
    the quartiles of the occupied bins' scores, and with them the fence,
    move by at most that share."""
    p99, occ = p99_by_bin(bins, values, n_bins)
    if not occ.any():
        return (0, 0), 0
    s = np.maximum(p99[occ], SKETCH_FLOOR)
    q1, q3 = np.percentile(s, [25.0, 75.0])
    exact = int(np.sum(s > q3 + FENCE_K * (q3 - q1)))
    e = SKETCH_REL
    hi_lo = (1 + FENCE_K) * q3 * (1 - e) - FENCE_K * q1 * (1 + e)
    hi_hi = (1 + FENCE_K) * q3 * (1 + e) - FENCE_K * q1 * (1 - e)
    sure = int(np.sum(s * (1 - e) > hi_hi))
    can = int(np.sum(s * (1 + e) > hi_lo))
    return (sure, can), exact


# --- the comparison -----------------------------------------------------------

def empty_numbers(limits: Dict = None) -> Dict[str, float]:
    return {k: 0 for k in (limits or LIMITS)}


def compare(got: Optional[Dict], want: Dict, numbers: Dict[str, float],
            notes: List[str], tag: str = "") -> None:
    """Fold one served answer (the ``/v1/query`` result object, or None
    when it never came) against the reference into ``numbers``."""
    if got is None:
        numbers["unanswered"] += 1
        notes.append(f"{tag}: no answer")
        return

    def miss(what: str, key: str) -> None:
        numbers[key] += 1
        if len(notes) < 20:
            notes.append(f"{tag}: {what}")

    if int(got.get("n_samples", -1)) != want["n_samples"]:
        miss(f"n_samples {got.get('n_samples')} vs {want['n_samples']}",
             "count_mismatch")
    if int(got.get("n_bins", -1)) != want["n_bins"]:
        miss(f"n_bins {got.get('n_bins')} vs {want['n_bins']}",
             "count_mismatch")
    g_groups = got.get("groups") or {}
    if sorted(g_groups) != sorted(want["groups"]):
        miss(f"groups {sorted(g_groups)} vs {sorted(want['groups'])}",
             "count_mismatch")
    for key, per in want["groups"].items():
        for m, w in per.items():
            g = (g_groups.get(key) or {}).get(m)
            where = f"group {key} {m}"
            if g is None:
                continue
            if int(g["count"]) != w["count"]:
                miss(f"{where} count {g['count']} vs {w['count']}",
                     "count_mismatch")
                continue
            for f in ("min", "max"):
                if np.float32(g[f]) != np.float32(w[f]):
                    miss(f"{where} {f} {g[f]!r} vs {w[f]!r}",
                         "minmax_mismatch")
            b, a = float(w["mean"]), float(g["mean"])
            if b == 0.0:
                gap = 0.0 if a == 0.0 else math.inf
            else:
                gap = abs(a - b) / (abs(b) * (w["nmax"] + 2) * U32)
            if gap > numbers["mean_gap"]:
                numbers["mean_gap"] = gap
                if gap > LIMITS["mean_gap"] and len(notes) < 20:
                    notes.append(f"{tag}: {where} mean {a!r} vs {b!r}")
    if "fence_range" in want:
        lo, hi = want["fence_range"]
        n = got.get("anomalous_bins")
        if n is None or not lo <= int(n) <= hi:
            miss(f"anomalous_bins {n} outside [{lo}, {hi}]",
                 "fence_outside")


def verdict(numbers: Dict[str, float], limits: Dict = None) -> bool:
    limits = limits or LIMITS
    return all(numbers[k] <= limits[k] for k in limits)


def lines(numbers: Dict[str, float], limits: Dict = None) -> List[str]:
    """Each number compared beside its limit, one per line."""
    limits = limits or LIMITS
    return [f"{k} {numbers[k]!r} limit {limits[k]!r}" for k in limits]


# --- the live cell: appends and fences ----------------------------------------

# The live fence is the p99 of k_stall per bin as the quantile sketch
# gives it (the midpoint of the log2 bucket, 8 per octave, of the bin's
# type-1 99th percentile, values below 1 counted as 1), fenced at
# Q3 + 1.5 IQR over the occupied bins. The device takes the bucket from
# a float32 log2: where the percentile lies within EDGE_TOL of a bucket
# edge (in units of an eighth of an octave) either neighbour counts.
SKETCH_SUBDIV = 8
SKETCH_BUCKETS = 384
MIDPOINTS = 2.0 ** ((np.arange(SKETCH_BUCKETS) + 0.5) / SKETCH_SUBDIV)
EDGE_TOL = 1e-5
MAX_AMBIGUOUS = 6

# The live cell adds two exact numbers to the query cells' (which it
# reads from the fence query's last answer): ticks whose anomalous bins
# differ, and fence transitions whose fence value differs. There,
# "unanswered" counts window batches that no fence event covered and
# "count_mismatch" ticks whose ingested rows differ too.
LIVE_LIMITS = dict(LIMITS, fence_mismatch=0, hi_fence_mismatch=0)


def sketch_p99(values: np.ndarray) -> Tuple[float, Optional[float]]:
    """A bin's p99 as the sketch gives it, and the neighbour bucket's
    midpoint where the device's float32 log2 may pick that one."""
    n = len(values)
    k = max(int(np.ceil(P99 * float(n))), 1)
    v = float(np.partition(values, k - 1)[k - 1])
    x = math.log2(max(v, SKETCH_FLOOR)) * SKETCH_SUBDIV
    b = min(max(int(math.floor(x)), 0), SKETCH_BUCKETS - 1)
    alt = None
    if x - math.floor(x) < EDGE_TOL and b > 0:
        alt = b - 1
    elif math.ceil(x) - x < EDGE_TOL and b < SKETCH_BUCKETS - 1:
        alt = b + 1
    return float(MIDPOINTS[b]), (None if alt is None
                                 else float(MIDPOINTS[alt]))


class LiveStore:
    """The store's k_stall values by bin, as appends extend it, and the
    p99 fence over them."""

    def __init__(self, table: Dict[str, np.ndarray], plan: Plan,
                 precision: str = "float64"):
        self.plan = plan
        self.precision = precision
        self.bins: Dict[int, List[np.ndarray]] = {}
        self._score: Dict[int, Tuple[float, Optional[float]]] = {}
        self._add(table["bin"], table["k_stall"])

    def _add(self, bins: np.ndarray, vals: np.ndarray) -> None:
        vals = _round(np.asarray(vals, np.float64), self.precision)
        for b in np.unique(bins):
            self.bins.setdefault(int(b), []).append(vals[bins == b])
            self._score.pop(int(b), None)

    def append(self, rows: Dict[str, np.ndarray], max_end: int) -> None:
        """Rows of one append; the plan grows in whole bins to cover the
        appended kernels' ends."""
        need = -(-(int(max_end) - self.plan.t_start) // self.plan.interval)
        self.plan.n = max(self.plan.n, need)
        if len(rows["k_start"]):
            self._add(self.plan.bin_of(rows["k_start"]), rows["k_stall"])

    def fences(self) -> List[Tuple[Tuple[int, ...], float]]:
        """Every (anomalous bins, fence) the sketch can give."""
        occ = sorted(self.bins)
        for b in occ:
            if b not in self._score:
                self._score[b] = sketch_p99(np.concatenate(self.bins[b]))
        base = np.array([self._score[b][0] for b in occ])
        amb = [i for i, b in enumerate(occ) if self._score[b][1] is not None]
        if len(amb) > MAX_AMBIGUOUS:
            amb = amb[:MAX_AMBIGUOUS]
        out = []
        for mask in range(1 << len(amb)):
            s = base.copy()
            for j, i in enumerate(amb):
                if mask >> j & 1:
                    s[i] = self._score[occ[i]][1]
            q1, q3 = np.percentile(s, [25.0, 75.0])
            hi = q3 + FENCE_K * (q3 - q1)
            out.append((tuple(b for b, x in zip(occ, s) if x > hi),
                        float(hi)))
        return out

    def answer(self, metric: str = "k_stall") -> Dict:
        """The fence query's answer over the whole store, as ``answer``
        gives it for a query."""
        vals = np.concatenate([np.concatenate(v) for v in
                               self.bins.values()])
        nmax = max(sum(len(x) for x in v) for v in self.bins.values())
        sizes = [len(a) for a, _ in self.fences()]
        return {"n_samples": int(len(vals)), "n_bins": int(self.plan.n),
                "group_by": None,
                "groups": {"0": {metric: {
                    "count": int(len(vals)),
                    "mean": float(vals.sum() / len(vals)),
                    "min": float(vals.min()), "max": float(vals.max()),
                    "nmax": nmax}}},
                "fence_range": (min(sizes), max(sizes)),
                "anomalous_bins": sizes[0]}


def append_rows(kern: Dict, mem: Dict, old_mem: Dict, rank: int,
                window_ns: int, cap: int) -> Tuple[Dict, int]:
    """One rank DB's rows of one append: its new kernels joined with its
    new memcpys and with the older memcpys that start within the join
    window of the new kernels' span. Returns (rows, last kernel end)."""
    if not len(kern["start"]):
        return None, 0
    ks, ke = _as_read(kern["start"]), _as_read(kern["end"])
    lo, hi = int(ks.min()) - window_ns, int(ke.max()) + window_ns
    near = (old_mem["start"] >= lo) & (old_mem["start"] < hi)
    both = {c: np.concatenate([np.asarray(old_mem[c])[near],
                               np.asarray(mem[c])]) for c in mem}
    return join_rows(kern, both, rank, window_ns, cap), int(ke.max())
