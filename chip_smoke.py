#!/usr/bin/env python3
"""Smoke run of the trace-analysis path on a TPU: one chip, or four.

Builds a 4-rank trace store at the paper's per-rank density (842,054
kernels and 107,045 memcpys per rank) from ``--seed`` through
``VariabilityPipeline.generate``, then answers a query batch cold on the
jax backend, so every shard goes through the device collective. The
batch runs through ``VariabilityPipeline.query`` and through the
``/v1/query`` HTTP service (``pipe.serve``); a repeated request must come
back as a summary-cache hit. Every device answer is compared with the
serial float64 host path on the same store, within the tolerances below.

  python3 chip_smoke.py             # one chip
  python3 chip_smoke.py --chips 4   # only the 4-device mesh path, compared
                                    # with the serial and one-chip answers

It refuses to run (non-zero exit, no result line) when JAX's first device
is not a TPU. Phase times printed here are smoke timings, not metrics.
The last line of standard output is the one-line JSON result.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "src"))

import numpy as np  # noqa: E402

from repro.core import (PipelineConfig, Query, QueryResult,  # noqa: E402
                        SyntheticSpec, TraceStore, VariabilityPipeline,
                        generate_synthetic, write_synthetic_dbs)
from repro.core.aggregation import DEVICE_DISPATCHES  # noqa: E402
from repro.core.reducers import QUANTILE_REL_ERR  # noqa: E402
from repro.serve import QueryClient  # noqa: E402
from repro.serve.query_service import _render_result  # noqa: E402

WORK_DIR = HERE / ".chip_smoke"
N_RANKS = 4
# the paper's per-rank density (Table 1); nothing is cut in width
PAPER_KERNELS_PER_RANK = 842_054
PAPER_MEMCPYS_PER_RANK = 107_045

# Tolerances of a float32 device answer against the float64 host path:
#  - counts are exact (float32 holds every per-cell count below 2**24);
#  - a cell's sum, sum of squares and mean agree within (n + 2) * 2**-24
#    relative, n being the cell's row count: the worst-case error of
#    float32 summation of n non-negative terms in any order, plus one
#    rounding for the square (the smoke's metrics are durations and
#    stall times, never negative);
#  - min and max equal the host value rounded to float32;
#  - a quantile answer is within the sketch's stated relative error of
#    the exact order statistic: device bucketize takes a float32 log2,
#    which may put a value on the other side of a bucket edge, but the
#    estimate stays within QUANTILE_REL_ERR (~4.4%) of the exact value;
#  - the same anomalous bins are flagged.
F32_UNIT_ROUNDOFF = 2.0 ** -24
QUANTILE_RTOL = 0.045
QUANTILE_Q = 0.99


def sum_rtol(count) -> np.ndarray:
    """Relative bound of a float32 sum over ``count`` rows (see above)."""
    return (np.asarray(count, np.float64) + 2.0) * F32_UNIT_ROUNDOFF


def log(msg: str) -> None:
    print(msg, flush=True)


def smoke_timing(phase: str, seconds: float) -> None:
    log(f"phase {phase}: {seconds:.3f} s (smoke timing, not a metric)")


class CompileLog:
    """Backend compiles (each one, persistent-cache hits included, goes
    through jax's backend-compile event) and persistent-cache hits."""

    def __init__(self) -> None:
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self) -> "CompileLog":
        import jax.monitoring as mon

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += float(secs)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def report(self, phase: str) -> None:
        log(f"compiles after {phase}: n={self.n} "
            f"seconds={self.seconds:.3f} "
            f"persistent_cache_hits={self.cache_hits}")


# --- store ------------------------------------------------------------------

def build_store(work: Path, seed: int, spec: SyntheticSpec) -> str:
    """Synthetic rank DBs from ``spec`` -> store, through the pipeline's
    own generation entry point (serial: in-process, no fork)."""
    t0 = time.perf_counter()
    ds = generate_synthetic(spec)
    paths = write_synthetic_dbs(ds, str(work / "dbs"))
    smoke_timing("write_rank_dbs", time.perf_counter() - t0)
    store_dir = str(work / "store")
    pipe = VariabilityPipeline(PipelineConfig(n_ranks=spec.n_ranks,
                                              backend="serial"))
    rep = pipe.generate(paths, store_dir)
    log(f"store: ranks={rep.n_ranks} shards={rep.n_shards} "
        f"kernels={rep.rows_per_table['KERNEL']} "
        f"memcpys={rep.rows_per_table['MEMCPY']} "
        f"joined_rows={rep.joined_rows} seed={seed}")
    smoke_timing("generate_store", rep.seconds)
    return store_dir


def smoke_queries(store_dir: str) -> List[Query]:
    man = TraceStore(store_dir).read_manifest()
    span = int(man.t_end - man.t_start)
    window = (int(man.t_start + span // 4), int(man.t_start + 3 * span // 4))
    return [
        Query(metrics=("k_stall", "m_duration"), group_by="k_device"),
        Query(metrics=("k_stall",), reducers=("moments", "quantile"),
              anomaly_score="p99"),
        Query(metrics=("k_stall",), transfer_kinds=(1,), time_window=window),
        # the widest segment space: 64 kernel names x bins, histogram too
        Query(metrics=("k_stall",), group_by="k_name",
              reducers=("moments", "quantile")),
    ]


def clear_caches(store_dir: str) -> None:
    store = TraceStore(store_dir)
    store.clear_summaries()
    store.clear_partials()


# --- comparisons ------------------------------------------------------------

def _fail(tag: str, what: str, bad: np.ndarray, got, want) -> None:
    i = tuple(int(x) for x in np.argwhere(bad)[0])
    raise AssertionError(
        f"{tag}: {what} differs in {int(bad.sum())} cell(s); first at "
        f"{i}: device {np.asarray(got)[i]!r} vs reference "
        f"{np.asarray(want)[i]!r}")


def _rel_check(tag: str, what: str, got, want, rtol: float) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    bad = np.abs(got - want) > rtol * np.abs(want)
    if bad.any():
        _fail(tag, what, bad, got, want)


def _eq_check(tag: str, what: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{tag}: {what} shape {got.shape} vs "
                             f"{want.shape}")
    bad = got != want
    if bad.any():
        _fail(tag, what, bad, got, want)


def exact_quantiles(store_dir: str, query: Query, result,
                    q: float = QUANTILE_Q) -> np.ndarray:
    """Exact type-1 (inverted-CDF) per-(bin, group) quantile of the
    query's first metric over the raw shard rows — the order statistic
    the sketch locates. Shape (n_bins, n_groups); 0.0 where empty."""
    store = TraceStore(store_dir)
    plan = result.plan
    keys = np.asarray(result.group_keys, np.float64).ravel()
    metric = query.metrics[0]
    cell_parts, val_parts = [], []
    for idx in store.shard_indices():
        cols = store.read_shard(idx)
        mask = query.row_mask(cols)
        sel = (slice(None) if mask is None else np.flatnonzero(mask))
        ts = np.asarray(cols["k_start"])[sel].astype(np.int64)
        if query.group_by is None:
            gid = np.zeros(ts.size, np.int64)
        else:
            gcol = np.asarray(cols[query.group_by], np.float64)[sel]
            gid = np.searchsorted(keys, gcol)
        cell_parts.append(plan.shard_of(ts) * len(keys) + gid)
        val_parts.append(np.asarray(cols[metric], np.float64)[sel])
    cell = np.concatenate(cell_parts)
    val = np.concatenate(val_parts)
    order = np.lexsort((val, cell))
    cell, val = cell[order], val[order]
    n_cells = plan.n_shards * len(keys)
    starts = np.searchsorted(cell, np.arange(n_cells), side="left")
    ends = np.searchsorted(cell, np.arange(n_cells), side="right")
    n = ends - starts
    k = np.maximum(np.ceil(q * n), 1).astype(np.int64)
    pick = np.where(n > 0, starts + k - 1, 0)
    out = np.where(n > 0, val[np.minimum(pick, max(len(val) - 1, 0))], 0.0)
    return out.reshape(plan.n_shards, len(keys))


def compare_to_host(tag: str, dev: QueryResult, ref: QueryResult,
                    exact_q=None) -> None:
    """A device answer against the serial float64 host answer."""
    a, b = dev.result, ref.result
    _eq_check(tag, "group keys", a.group_keys, b.group_keys)
    ga, gb = a.grouped, b.grouped
    _eq_check(tag, "count", ga.count, gb.count)
    rtol = sum_rtol(gb.count)
    _rel_check(tag, "sum", ga.sum, gb.sum, rtol)
    _rel_check(tag, "sumsq", ga.sumsq, gb.sumsq, rtol)
    occ = gb.count > 0
    with np.errstate(invalid="ignore", divide="ignore"):
        _rel_check(tag, "mean", np.where(occ, ga.sum / gb.count, 0.0),
                   np.where(occ, gb.sum / gb.count, 0.0), rtol)
    for f in ("min", "max"):
        want = np.where(occ, getattr(gb, f), 0.0).astype(np.float32)
        _eq_check(tag, f, np.where(occ, getattr(ga, f), 0.0), want)
    _eq_check(tag, "anomalous bins", dev.anomalies.flags,
              ref.anomalies.flags)
    if exact_q is not None:
        ska, skb = a.reduced["quantile"], b.reduced["quantile"]
        _eq_check(tag, "sketch totals", ska.total(), skb.total())
        mi = list(a.metrics).index(dev.query.metrics[0])
        for side, sk in (("device", ska), ("host", skb)):
            est = sk.quantile(QUANTILE_Q)[..., mi]
            _rel_check(f"{tag} ({side} p{QUANTILE_Q * 100:g})",
                       "quantile vs exact", est, exact_q, QUANTILE_RTOL)


def compare_meshes(tag: str, got: QueryResult, one: QueryResult) -> None:
    """A many-device answer against the one-device answer: the same
    float32 arithmetic in another partition order."""
    a, b = got.result, one.result
    _eq_check(tag, "group keys", a.group_keys, b.group_keys)
    _eq_check(tag, "count", a.grouped.count, b.grouped.count)
    # each side is within one bound of the exact value
    rtol = 2.0 * sum_rtol(b.grouped.count)
    _rel_check(tag, "sum", a.grouped.sum, b.grouped.sum, rtol)
    _rel_check(tag, "sumsq", a.grouped.sumsq, b.grouped.sumsq, rtol)
    _eq_check(tag, "min", a.grouped.min, b.grouped.min)
    _eq_check(tag, "max", a.grouped.max, b.grouped.max)
    _eq_check(tag, "anomalous bins", got.anomalies.flags,
              one.anomalies.flags)
    if "quantile" in a.reduced:
        _eq_check(tag, "sketch counts", a.reduced["quantile"].counts,
                  b.reduced["quantile"].counts)


def compare_rendered(tag: str, got: Dict, want: Dict,
                     rtol: float) -> None:
    """An HTTP answer against the same rendering of the host answer; a
    rendered mean folds every bin of its group, so ``rtol`` is the bound
    of the query's fullest cell."""
    for key in ("n_samples", "n_bins", "group_by", "anomalous_bins"):
        if got.get(key) != want.get(key):
            raise AssertionError(f"{tag}: {key} {got.get(key)!r} vs "
                                 f"{want.get(key)!r}")
    if sorted(got["groups"]) != sorted(want["groups"]):
        raise AssertionError(f"{tag}: group keys differ")
    for gk, per_metric in want["groups"].items():
        for m, w in per_metric.items():
            g = got["groups"][gk][m]
            where = f"{tag} group {gk} {m}"
            if g["count"] != w["count"]:
                raise AssertionError(f"{where}: count {g['count']} vs "
                                     f"{w['count']}")
            _rel_check(where, "mean", g["mean"], w["mean"], rtol)
            for f in ("min", "max"):
                _eq_check(where, f, np.float32(g[f]), np.float32(w[f]))


def check_placement(devices: Sequence, queries: Sequence[Query]) -> None:
    """Every device dispatch held each row input as one section per mesh
    device, in mesh order, each device holding only its own section.
    Logs each dispatch's segment count with the queries fused into it
    (one dispatch per reducer suite)."""
    ids = [int(d.id) for d in devices]
    if not DEVICE_DISPATCHES:
        raise AssertionError("no device dispatch ran")
    for disp in DEVICE_DISPATCHES:
        for name, sections in disp.placement.items():
            width = sections[-1][2] // len(ids)
            want = tuple((i, k * width, (k + 1) * width)
                         for k, i in enumerate(ids))
            if tuple(sections) != want:
                raise AssertionError(
                    f"input {name!r} placed as {sections}, expected one "
                    f"section per device {want}")
        fused = [i for i, q in enumerate(queries)
                 if q.canonical_reducers == disp.reducers]
        log(f"dispatch: reducers={','.join(disp.reducers)} "
            f"queries={fused} rows={disp.rows} n_seg={disp.n_seg} "
            f"n_seg_dev={disp.n_seg_dev} devices={ids} "
            f"placement=own-section-per-device")


# --- phases -----------------------------------------------------------------

def host_reference(store_dir: str, queries: Sequence[Query]
                   ) -> List[QueryResult]:
    t0 = time.perf_counter()
    pipe = VariabilityPipeline(PipelineConfig(n_ranks=N_RANKS,
                                              backend="serial"))
    out = pipe.query(store_dir, queries)
    smoke_timing("serial_reference", time.perf_counter() - t0)
    return out


def device_answers(store_dir: str, queries: Sequence[Query],
                   devices: Sequence, tag: str) -> List[QueryResult]:
    """The batch cold on the jax backend over ``devices``: every shard
    dirty, every row through the device collective."""
    clear_caches(store_dir)
    DEVICE_DISPATCHES.clear()
    n_files = len(TraceStore(store_dir).shard_indices())
    t0 = time.perf_counter()
    pipe = VariabilityPipeline(PipelineConfig(
        n_ranks=N_RANKS, backend="jax", devices=list(devices)))
    out = pipe.query(store_dir, queries)
    smoke_timing(tag, time.perf_counter() - t0)
    for q, qr in zip(queries, out):
        if qr.cache_hit or qr.partial_hits or qr.rows_scanned <= 0 or \
                qr.recomputed_shards + qr.shards_pruned != n_files:
            raise AssertionError(f"{tag}: query {q.to_spec()} was not "
                                 f"cold: {qr.provenance()}")
        log(f"{tag}: {q.to_spec()} rows_scanned={qr.rows_scanned} "
            f"{qr.provenance()}")
    check_placement(devices, queries)
    return out


def query_and_compare(store_dir: str, devices: Sequence,
                      http: bool = True
                      ) -> Tuple[List[QueryResult], List[QueryResult]]:
    """The smoke's query-and-compare phase on ``devices``: host
    reference, the jax answers through ``VariabilityPipeline.query`` and
    (with ``http``) through ``POST /v1/query``, each compared with the
    host path. Raises on any mismatch; returns (host, device) results."""
    queries = smoke_queries(store_dir)
    ref = host_reference(store_dir, queries)
    exact = [exact_quantiles(store_dir, q, r.result)
             if "quantile" in q.canonical_reducers else None
             for q, r in zip(queries, ref)]
    dev = device_answers(store_dir, queries, devices, "jax_pipeline")
    for i, (d, r, e) in enumerate(zip(dev, ref, exact)):
        compare_to_host(f"pipeline query {i}", d, r, e)
    log(f"pipeline answers match the serial host path "
        f"({len(queries)} queries)")
    if http:
        serve_and_compare(store_dir, queries, ref, devices)
    return ref, dev


def serve_and_compare(store_dir: str, queries: Sequence[Query],
                      ref: Sequence[QueryResult], devices: Sequence) -> None:
    clear_caches(store_dir)
    DEVICE_DISPATCHES.clear()
    pipe = VariabilityPipeline(PipelineConfig(
        n_ranks=N_RANKS, backend="jax", devices=list(devices)))
    svc = pipe.serve(store_dir, port=0, request_timeout_s=900.0)
    try:
        client = QueryClient(port=svc.cfg.port, timeout_s=960.0)
        if not client.wait_healthy(timeout_s=30.0):
            raise AssertionError("query service never became healthy")
        want = [_render_result(r) for r in ref]
        for attempt in ("cold", "repeat"):
            t0 = time.perf_counter()
            body = client.query_raw(list(queries))
            smoke_timing(f"http_{attempt}", time.perf_counter() - t0)
            for i, (got, w) in enumerate(zip(body["results"], want)):
                hit = bool(got["cache_hit"])
                if hit != (attempt == "repeat"):
                    raise AssertionError(
                        f"http {attempt} query {i}: cache_hit={hit} "
                        f"({got['provenance']})")
                compare_rendered(f"http {attempt} query {i}", got, w,
                                 float(sum_rtol(
                                     ref[i].result.grouped.count.max())))
                log(f"http {attempt} query {i}: {got['provenance']}")
            if attempt == "cold":
                check_placement(devices, queries)
        log("http answers match the serial host path; the repeat was a "
            "summary-cache hit")
    finally:
        svc.stop()


def four_chip_phase(store_dir: str, devices: Sequence) -> None:
    """The same batch on a 4-device mesh, compared with the serial host
    path and with the one-device jax answers."""
    queries = smoke_queries(store_dir)
    ref = host_reference(store_dir, queries)
    exact = [exact_quantiles(store_dir, q, r.result)
             if "quantile" in q.canonical_reducers else None
             for q, r in zip(queries, ref)]
    one = device_answers(store_dir, queries, devices[:1], "jax_one_chip")
    four = device_answers(store_dir, queries, devices, "jax_four_chips")
    for i in range(len(queries)):
        compare_to_host(f"one-chip query {i}", one[i], ref[i], exact[i])
        compare_to_host(f"four-chip query {i}", four[i], ref[i], exact[i])
        compare_meshes(f"four vs one chip query {i}", four[i], one[i])
    log(f"four-chip answers match the serial host path and the one-chip "
        f"answers ({len(queries)} queries)")


# --- entry point ------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic trace")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the whole smoke on one chip; 4: only the "
                         "4-device mesh path and what it is compared with")
    args = ap.parse_args(argv)

    import jax
    all_devs = jax.devices()
    first = all_devs[0]
    if first.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{first.platform!r} ({first.device_kind})", file=sys.stderr)
        return 2
    if len(all_devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX shows {len(all_devs)}", file=sys.stderr)
        return 2
    devices = all_devs[:args.chips]
    coords = [(int(d.id), tuple(getattr(d, "coords", ()))) for d in all_devs]
    log(f"device: platform={first.platform} kind={first.device_kind} "
        f"count={len(all_devs)} mesh={[int(d.id) for d in devices]} "
        f"all={coords}")

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileLog().install()

    errors: List[str] = []
    default_hook = threading.excepthook

    def on_thread_error(hook_args) -> None:
        errors.append(f"{hook_args.thread.name}: "
                      f"{hook_args.exc_type.__name__}: {hook_args.exc_value}")
        default_hook(hook_args)

    threading.excepthook = on_thread_error
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    WORK_DIR.mkdir(parents=True)
    try:
        spec = SyntheticSpec(n_ranks=N_RANKS,
                             kernels_per_rank=PAPER_KERNELS_PER_RANK,
                             memcpys_per_rank=PAPER_MEMCPYS_PER_RANK,
                             seed=args.seed)
        store_dir = build_store(WORK_DIR, args.seed, spec)
        if args.chips == 1:
            query_and_compare(store_dir, devices, http=True)
        else:
            four_chip_phase(store_dir, devices)
        compiles.report("all phases")
    finally:
        threading.excepthook = default_hook
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    if errors:
        raise AssertionError(f"service thread(s) raised: {errors}")
    print(json.dumps({"ok": True,
                      "device": {"platform": first.platform,
                                 "kind": first.device_kind,
                                 "count": len(all_devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
