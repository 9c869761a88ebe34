"""Analyze GPU-profiler trace DBs with the sharded pipeline (any backend).

  PYTHONPATH=src python examples/analyze_trace.py --db rank0.sqlite \\
      --db rank1.sqlite --ranks 4 --backend process --interval-ms 1000 \\
      --metric k_stall --metric m_duration --group-by k_device \\
      --score p99

Without --db, a synthetic dataset is generated (useful demo mode). Prints
the Fig-1a/1b analyses: per-bin stall stats, top-variability intervals and
the transfer-direction byte breakdown — plus, with several --metric flags
and/or --group-by, the one-pass multi-metric grouped summary. A quantile
score (``--score p99`` / ``p95`` / ``iqr``) adds the quantile-sketch
reducer and fences on the within-bin duration distribution instead of the
bin mean. Repeat aggregations over the same store are answered from the
summary cache (``summary_*.npz``) without re-reading shards.

Trace diff & regression gating (the CI verdict pipeline):

  # build a baseline store and a candidate store (same workload, the
  # candidate respecialized + slowed 1.5x on one kernel family) ...
  python examples/analyze_trace.py --prepare-store /tmp/base --seed 7
  python examples/analyze_trace.py --prepare-store /tmp/cand --seed 7 \\
      --name-variant 1 --slowdown 1.5
  # ... then diff them: ranked "what got slower and where" report,
  # exit 1 when the verdict is "regressed"
  python examples/analyze_trace.py --diff /tmp/base /tmp/cand \\
      --diff-out verdict.json

Ingesting real profiler traces (Nsight Systems / nvprof SQLite exports):

  # sniff + ingest exported traces through the TraceSource adapter —
  # the schema dialect is detected per file, reads are chunk-bounded
  python examples/analyze_trace.py --ingest-nsight report0.sqlite \\
      --ingest-nsight report1.sqlite --ranks 2
  # selective ingest: push the predicates into the SQLite reads and
  # print how many rows were skipped SQL-side
  python examples/analyze_trace.py --ingest-nsight report0.sqlite \\
      --push-window 5000000000 9000000000 --push-names 0,1,2,3
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import (GenerationConfig, PipelineConfig, SyntheticSpec,
                        VariabilityPipeline, generate_synthetic,
                        write_synthetic_dbs)
from repro.core.anomaly import top_variability_bins
from repro.compile_cache import enable_compile_cache
from repro.core.events import COPY_KIND_NAMES


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--db", action="append", default=[],
                    help="rank SQLite DB (repeatable)")
    ap.add_argument("--ingest-nsight", action="append", default=[],
                    metavar="EXPORT.sqlite",
                    help="real profiler SQLite export (Nsight Systems "
                         "or nvprof; repeatable) — sniffed, then "
                         "ingested through the TraceSource adapter "
                         "exactly like a --db rank DB")
    ap.add_argument("--push-window", nargs=2, type=int, default=None,
                    metavar=("T0_NS", "T1_NS"),
                    help="ingest-time pushdown: only kernels with "
                         "start in [T0, T1) are read from the source "
                         "DBs (compiled into the SQLite WHERE clause)")
    ap.add_argument("--push-names", default=None, metavar="ID,ID,...",
                    help="ingest-time pushdown: comma-separated kernel "
                         "name ids to keep at read time")
    ap.add_argument("--push-ranks", default=None, metavar="R,R,...",
                    help="ingest-time pushdown: comma-separated source "
                         "DB indices to ingest; others are skipped "
                         "whole (counted, never read)")
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--backend", default="process",
                    choices=["serial", "process", "jax"])
    ap.add_argument("--interval-ms", type=float, default=1000.0)
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--metric", action="append", default=[],
                    help="metric column (repeatable; default k_stall)")
    ap.add_argument("--group-by", default=None,
                    help="group column, e.g. k_device, k_name, m_kind")
    ap.add_argument("--score", default="mean",
                    help="anomaly score: mean/std/max/sum (moments) or "
                         "p50/p95/p99/iqr (quantile sketch)")
    ap.add_argument("--append-demo", action="store_true",
                    help="after the analysis, append a late-arriving "
                         "synthetic rank DB and delta-aggregate (only "
                         "dirty/new shards are rescanned)")
    ap.add_argument("--prepare-store", default=None, metavar="DIR",
                    help="generate a synthetic trace store at DIR and "
                         "exit (for --diff / the trace-regression CI "
                         "workflow); shaped by --seed, --name-variant "
                         "and --slowdown")
    ap.add_argument("--seed", type=int, default=7,
                    help="synthetic workload seed for --prepare-store")
    ap.add_argument("--name-variant", type=int, default=0,
                    help="kernel-name respecialization variant for "
                         "--prepare-store (same data, different "
                         "mangled/Triton spellings)")
    ap.add_argument("--slowdown", type=float, default=None,
                    help="with --prepare-store: inject this slowdown "
                         "factor into one kernel family (layer_norm)")
    ap.add_argument("--diff", nargs=2, metavar=("STORE_A", "STORE_B"),
                    default=None,
                    help="diff two trace stores: print the ranked "
                         "regression report and exit 1 if the verdict "
                         "is 'regressed'")
    ap.add_argument("--diff-out", default=None, metavar="FILE",
                    help="with --diff: also write the machine-readable "
                         "verdict record (check_bench shape) to FILE")
    ap.add_argument("--diff-cached", action="store_true",
                    help="with --diff: require the report to come from "
                         "the diff-result cache (exit non-zero if it "
                         "was recomputed) — for workflows asserting a "
                         "repeat comparison is free")
    ap.add_argument("--query", default=None,
                    help="JSON list of declarative query specs (inline, "
                         "or @file.json) — run as ONE fused batch over "
                         "the store and print each query's answer plus "
                         "provenance (cache hit / shards pruned / rows "
                         "filtered). Example: '[{\"metrics\": "
                         "[\"k_stall\"], \"group_by\": \"m_kind\", "
                         "\"transfer_kinds\": [1, 2]}]'")
    args = ap.parse_args()
    if args.backend == "jax":
        enable_compile_cache()

    if args.prepare_store:
        _prepare_store(args)
        return
    if args.diff:
        _diff(args)
        return

    tmp = tempfile.mkdtemp(prefix="repro_analyze_")
    db_paths = list(args.db)
    if args.ingest_nsight:
        from repro.ingest import sniff_schema
        print("sniffing profiler exports:")
        for p in args.ingest_nsight:
            s = sniff_schema(p)
            print(f"  {p}: dialect={s.kind} kernel_table={s.kernel_table}"
                  f" names={s.string_table or '(none)'}"
                  f" stall={'yes' if s.stall_col else 'no'}")
        db_paths += list(args.ingest_nsight)
    if not db_paths:
        print("no --db given: generating a synthetic dataset")
        ds = generate_synthetic(SyntheticSpec(n_ranks=2))
        db_paths = write_synthetic_dbs(ds, os.path.join(tmp, "dbs"))

    pushdown = _pushdown_from_args(args)
    metrics = args.metric or ["k_stall"]
    # a quantile-family score pulls the "quantile" reducer into the suite
    # automatically (PipelineConfig.reducer_suite)
    cfg = PipelineConfig(
        n_ranks=args.ranks, backend=args.backend, top_k=args.top_k,
        metrics=metrics, group_by=args.group_by,
        anomaly_score=args.score,
        generation=GenerationConfig(
            interval_ns=int(args.interval_ms * 1e6),
            pushdown=pushdown))
    pipe = VariabilityPipeline(cfg)
    res = pipe.run(db_paths, os.path.join(tmp, "store"))
    gen = res.generation
    if gen.ingest_rows_read or gen.ingest_rows_skipped:
        total = gen.ingest_rows_read + gen.ingest_rows_skipped
        print(f"ingest: {gen.ingest_rows_read:,} event rows read, "
              f"{gen.ingest_rows_skipped:,} skipped by pushdown "
              f"({total:,} in range)")

    stats = res.aggregation.stats
    occ = stats.count > 0
    print(f"\n=== {len(db_paths)} DBs, {res.generation.n_shards} shards, "
          f"{int(stats.count.sum()):,} samples ===")
    print(f"gen {res.gen_seconds:.2f}s | agg {res.agg_seconds:.2f}s")
    print(f"{metrics[0]} mean={stats.mean[occ].mean():.3g} "
          f"std={stats.std[occ].mean():.3g}")

    print(f"\ntop-{args.top_k} anomalous intervals (IQR fence "
          f"{res.anomalies.hi_fence:.3g}):")
    for (t0, t1), i in zip(res.anomaly_windows, res.anomalies.top_idx):
        print(f"  [{t0} .. {t1})  score={res.anomalies.scores[i]:.4g}")

    top = top_variability_bins(stats, 0.95)
    print(f"\ntop-5% variability bins: {top[:10].tolist()}")

    print("\ntransfer bytes by direction (Fig 1b):")
    for kind, per_bin in sorted(res.aggregation.copy_kind_bytes.items()):
        name = COPY_KIND_NAMES.get(kind, str(kind))
        print(f"  {name:8s}: {np.sum(per_bin):.4g} bytes")

    # -- one-pass multi-metric × group-by summary --------------------------
    agg = res.aggregation
    if len(metrics) > 1 or args.group_by:
        print(f"\nmulti-metric summary "
              f"({len(metrics)} metrics x "
              f"{len(agg.group_keys)} groups of "
              f"{args.group_by or '<all>'}):")
        for g in agg.group_keys:
            parts = []
            for m in metrics:
                s = agg.select(metric=m, group=float(g))
                o = s.count > 0
                mean = s.mean[o].mean() if o.any() else 0.0
                parts.append(f"{m}={mean:.4g}")
            print(f"  {args.group_by or 'all'}={g:g}: "
                  f"n={int(agg.select(0, float(g)).count.sum()):8d}  "
                  + "  ".join(parts))

    # the second aggregate over the same store hits the summary cache
    again = pipe.aggregate(os.path.join(tmp, "store"))
    print(f"\nre-analysis: {again.seconds*1e3:.1f}ms "
          f"(from_cache={again.from_cache}, "
          f"first pass {agg.seconds*1e3:.1f}ms)")

    if args.query:
        _query_demo(pipe, os.path.join(tmp, "store"), args.query)

    if args.append_demo:
        _append_demo(pipe, os.path.join(tmp, "store"), db_paths, tmp)


def _pushdown_from_args(args):
    """Compile the --push-* flags into an ingest-time pushdown Query."""
    if not (args.push_window or args.push_names or args.push_ranks):
        return None
    from repro.core import Query
    return Query(
        time_window=(tuple(args.push_window) if args.push_window else None),
        kernel_names=(tuple(int(x) for x in args.push_names.split(","))
                      if args.push_names else None),
        ranks=(tuple(int(x) for x in args.push_ranks.split(","))
               if args.push_ranks else None))


# one kernel family ("layer_norm": synthetic name ids congruent mod 21)
# across its mangled / Triton / template spellings
_SLOW_IDS = (3, 24, 45)


def _prepare_store(args) -> None:
    """Generate a synthetic store for the trace-regression workflow:
    same seed = same workload; --name-variant respecializes the kernel
    spellings; --slowdown injects a regression into one family."""
    from repro.core import inject_slowdown, run_generation

    ds = generate_synthetic(SyntheticSpec(
        n_ranks=args.ranks, seed=args.seed,
        name_variant=args.name_variant))
    if args.slowdown is not None:
        ds = inject_slowdown(ds, args.slowdown, _SLOW_IDS)
    tmp = tempfile.mkdtemp(prefix="repro_prepare_")
    dbs = write_synthetic_dbs(ds, os.path.join(tmp, "dbs"))
    rep = run_generation(dbs, args.prepare_store, n_ranks=args.ranks)
    print(f"store ready: {args.prepare_store} ({rep.n_shards} shards, "
          f"seed={args.seed}, variant={args.name_variant}"
          + (f", slowdown x{args.slowdown:g} on ids {list(_SLOW_IDS)}"
             if args.slowdown is not None else "") + ")")


def _diff(args) -> None:
    """Diff two stores and gate on the verdict (exit 1 = regressed)."""
    cfg = PipelineConfig(n_ranks=args.ranks, backend=args.backend,
                         metrics=args.metric or ["k_stall"])
    rep = VariabilityPipeline(cfg).diff(args.diff[0], args.diff[1])
    print(rep.render())
    print(f"\nprovenance: {rep.provenance()}")
    print(f"diff-cached: {rep.from_cache}")
    if args.diff_cached and not rep.from_cache:
        raise SystemExit(
            "--diff-cached: report was recomputed, not served from the "
            "diff-result cache")
    if args.diff_out:
        with open(args.diff_out, "w") as f:
            f.write(rep.to_json() + "\n")
        print(f"verdict record written to {args.diff_out}")
    if rep.verdict == "regressed":
        raise SystemExit(1)


def _query_demo(pipe, store_dir, spec_arg) -> None:
    """Run a JSON batch of declarative queries as ONE fused scan and
    print each answer with its execution provenance."""
    import json

    from repro.core import Query

    blob = (open(spec_arg[1:]).read() if spec_arg.startswith("@")
            else spec_arg)
    specs = json.loads(blob)
    if isinstance(specs, dict):
        specs = [specs]
    queries = [Query.from_spec(s) for s in specs]
    results = pipe.query(store_dir, queries)
    print(f"\n=== fused query batch: {len(queries)} queries, "
          f"one shard scan ===")
    for qr in results:
        q = qr.query
        desc = ",".join(q.metrics) + (f" by {q.group_by}" if q.group_by
                                      else "")
        preds = []
        if q.time_window:
            preds.append(f"window=[{q.time_window[0]},{q.time_window[1]})")
        if q.ranks is not None:
            preds.append(f"ranks={list(q.ranks)}")
        if q.kernel_names is not None:
            preds.append(f"names={list(q.kernel_names)}")
        if q.transfer_kinds is not None:
            preds.append(f"kinds={list(q.transfer_kinds)}")
        s = qr.result.stats
        occ = s.count > 0
        mean = s.mean[occ].mean() if occ.any() else 0.0
        print(f"  [{desc}] {' '.join(preds) or '(no predicates)'}")
        print(f"    n={int(s.count.sum()):,} mean={mean:.4g} "
              f"{q.anomaly_score}-anomalies="
              f"{int(qr.anomalies.flags.sum())}")
        print(f"    provenance: {qr.provenance()}")


def _append_demo(pipe, store_dir, db_paths, tmp) -> None:
    """The automated-workflow loop on synthetic data: a late-arriving
    rank DB is appended onto the live store, the delta aggregation
    rescans only the shards it dirtied, and the fences are refreshed."""
    import dataclasses

    from repro.core import generate_synthetic, write_rank_db

    from repro.core import TraceStore

    # a short burst, so only the few shards it overlaps become dirty;
    # re-based onto the STORE's own time range (append loudly rejects
    # events before t_start, and real --db traces live on an arbitrary
    # epoch — never assume the synthetic one)
    late = generate_synthetic(dataclasses.replace(
        SyntheticSpec(n_ranks=1), seed=123, kernels_per_rank=2000,
        memcpys_per_rank=200, duration_s=5.0, n_anomaly_windows=1))
    tr = late.traces[0]
    man = TraceStore(store_dir).read_manifest()
    span = max(int(tr.kernels.end.max() - tr.kernels.start.min()), 1)
    shift = (man.t_start + (man.t_end - man.t_start) // 3
             - int(tr.kernels.start.min()))
    if man.t_end - man.t_start <= span:     # tiny store: land at t_start
        shift = man.t_start - int(tr.kernels.start.min())
    for ev in (tr.kernels, tr.memcpys):
        ev.start = ev.start + shift
        ev.end = ev.end + shift
    late_path = os.path.join(tmp, "late_rank.sqlite")
    write_rank_db(late_path, tr)
    res = pipe.append([late_path], store_dir)
    rep, agg = res.generation, res.aggregation
    print(f"\nappend demo: +{rep.appended_rows:,} rows from a late rank "
          f"DB ({rep.n_new_shards} new shards, "
          f"{len(rep.dirty_shards)} dirtied) in {rep.seconds:.2f}s")
    if agg.recomputed_shards is not None:
        detail = (f"rescanned {len(agg.recomputed_shards)}/"
                  f"{agg.plan.n_shards} shards, "
                  f"{agg.partial_hits} from the partial cache")
    else:   # jax backend: full on-device rescan, no partial cache
        detail = f"full rescan of {agg.plan.n_shards} shards (jax backend)"
    print(f"delta re-analysis: {agg.seconds*1e3:.1f}ms — {detail}")
    print(f"refreshed top anomaly windows: "
          f"{res.anomaly_windows[:3].tolist()}")


if __name__ == "__main__":
    main()
