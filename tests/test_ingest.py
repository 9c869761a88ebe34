"""Real profiler ingestion frontend tests: schema sniffing across the
nvprof / Nsight Systems / native SQLite dialects, fixture ingests
building stores bit-identical to direct synthetic builds (serial AND
process backends), chunked reads matching one-shot reads bitwise,
ingest-time predicate pushdown matching the post-hoc filter oracle
(with provable SQL-side row skipping), loud rejection of malformed
exports, name-table spelling tolerance with ``kernel_{id}`` fallback,
streaming tails of a live-written Nsight export, and the diff engine
running against two ingested real-trace stores."""

import dataclasses
import os
import sqlite3
import time

import numpy as np
import pytest

from repro.core import (GenerationConfig, PipelineConfig, Query,
                        SyntheticSpec, TraceStore, VariabilityPipeline,
                        generate_synthetic, inject_slowdown,
                        run_aggregation, run_generation, trace_remainder,
                        truncate_trace, write_synthetic_dbs)
from repro.core.events import (RankTrace, append_rank_db, read_kernel_names,
                               write_rank_db)
from repro.ingest import (IngestError, SqliteTraceSource,
                          append_fixture_rank_db, as_trace_source,
                          rowid_watermark, sniff_schema, write_fixture_dbs,
                          write_nsys_rank_db, write_nvprof_rank_db)

_NS = 1_000_000_000
SUITE_QUERY = Query(metrics=("k_stall", "m_duration"), group_by="src_rank",
                    reducers=("moments", "quantile"))


@pytest.fixture(scope="module")
def trio(tmp_path_factory):
    """One synthetic workload written three ways: native rank DBs plus
    bit-faithful nvprof- and Nsight-schema fixture exports."""
    root = tmp_path_factory.mktemp("ingest_trio")
    ds = generate_synthetic(SyntheticSpec(
        n_ranks=2, kernels_per_rank=3000, memcpys_per_rank=400,
        duration_s=16.0, n_anomaly_windows=2, seed=11))
    native = write_synthetic_dbs(ds, str(root / "native"))
    nvprof = write_fixture_dbs(ds, str(root / "nvprof"), flavor="nvprof")
    nsys = write_fixture_dbs(ds, str(root / "nsys"), flavor="nsys")
    return ds, native, nvprof, nsys, root


@pytest.fixture(scope="module")
def native_store(trio):
    _, native, _, _, root = trio
    out = str(root / "store_native")
    run_generation(native, out, n_ranks=2)
    return out


def _assert_stores_bit_identical(a_dir, b_dir):
    """Every shard file's every column bit-equal, same plan, same
    manifest kernel-name table (source paths/kinds legitimately
    differ)."""
    sa, sb = TraceStore(a_dir), TraceStore(b_dir)
    ma, mb = sa.read_manifest(), sb.read_manifest()
    assert (ma.t_start, ma.t_end, ma.n_shards) == \
        (mb.t_start, mb.t_end, mb.n_shards)
    assert ma.extra["kernel_names"] == mb.extra["kernel_names"]
    for s in range(ma.n_shards):
        ca, cb = sa.read_shard(s), sb.read_shard(s)
        assert set(ca) == set(cb)
        for col in ca:
            np.testing.assert_array_equal(ca[col], cb[col])


# --- schema sniffing --------------------------------------------------------

def test_sniff_classifies_all_three_dialects(trio):
    _, native, nvprof, nsys, _ = trio
    s = sniff_schema(native[0])
    assert s.kind == "native"
    assert s.kernel_table == "CUPTI_ACTIVITY_KIND_KERNEL"
    assert s.name_col == "shortName" and s.string_table == "StringIds"
    assert s.stall_col == "memoryStall"

    s = sniff_schema(nvprof[0])
    assert s.kind == "nvprof"
    assert s.kernel_table == "CUPTI_ACTIVITY_KIND_CONCURRENT_KERNEL"
    assert s.name_col == "name" and s.string_table == "StringTable"
    assert s.string_id_col == "_id_"
    assert s.device_table == "CUPTI_ACTIVITY_KIND_DEVICE"
    assert s.has_runtime

    s = sniff_schema(nsys[0])
    assert s.kind == "nsys"
    assert s.kernel_table == "CUPTI_ACTIVITY_KIND_KERNEL"
    assert s.name_col == "shortName" and s.string_table == "StringIds"
    assert s.device_table == "TARGET_INFO_GPU"


def test_sniff_rejects_malformed_inputs(tmp_path):
    with pytest.raises(IngestError, match="does not exist"):
        sniff_schema(str(tmp_path / "nope.sqlite"))

    garbage = tmp_path / "garbage.sqlite"
    garbage.write_bytes(b"this is not a sqlite file" * 100)
    with pytest.raises(IngestError, match="not a readable SQLite"):
        sniff_schema(str(garbage))

    empty = tmp_path / "empty.sqlite"
    conn = sqlite3.connect(str(empty))
    conn.execute("CREATE TABLE unrelated (x INTEGER)")
    conn.commit()
    conn.close()
    with pytest.raises(IngestError, match="no CUPTI kernel activity"):
        sniff_schema(str(empty))

    # kernel table present but missing required columns
    partial = tmp_path / "partial.sqlite"
    conn = sqlite3.connect(str(partial))
    conn.execute("CREATE TABLE CUPTI_ACTIVITY_KIND_KERNEL (start INTEGER)")
    conn.commit()
    conn.close()
    with pytest.raises(IngestError, match="missing required column"):
        sniff_schema(str(partial))


def test_truncated_database_fails_loudly(trio, tmp_path):
    """A fixture whose file is cut mid-page must raise IngestError from
    the read, never ingest a partial guess."""
    ds, _, _, _, _ = trio
    p = str(tmp_path / "trunc.sqlite")
    write_nvprof_rank_db(p, ds.traces[0])
    src = as_trace_source(p)       # sniff succeeds on the intact header
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    with pytest.raises(IngestError):
        src.read(rank=0)


# --- fixture -> ingest -> store bit-identity --------------------------------

@pytest.mark.parametrize("flavor", ["nvprof", "nsys"])
def test_fixture_ingest_bit_identical_serial(trio, native_store, flavor):
    _, _, nvprof, nsys, root = trio
    paths = nvprof if flavor == "nvprof" else nsys
    out = str(root / f"store_{flavor}_serial")
    rep = run_generation(paths, out, n_ranks=2)
    assert rep.ingest_rows_read > 0 and rep.ingest_rows_skipped == 0
    _assert_stores_bit_identical(native_store, out)
    man = TraceStore(out).read_manifest()
    assert set(man.extra["source_kinds"].values()) == {flavor}


@pytest.mark.parametrize("flavor", ["nvprof", "nsys"])
def test_fixture_ingest_bit_identical_process_backend(trio, native_store,
                                                      flavor):
    """The process backend pickles TraceSources into its rank workers;
    the resulting store must still be bit-identical, and the per-worker
    ingest counters must survive the pool round-trip into the report."""
    _, _, nvprof, nsys, root = trio
    paths = nvprof if flavor == "nvprof" else nsys
    out = str(root / f"store_{flavor}_process")
    pipe = VariabilityPipeline(PipelineConfig(n_ranks=2, backend="process"))
    rep = pipe.generate(paths, out)
    assert rep.ingest_rows_read > 0
    _assert_stores_bit_identical(native_store, out)


def test_chunked_reads_match_oneshot(trio, native_store):
    """chunk_rows=7 forces hundreds of rowid windows per table; the
    store must come out bitwise equal to the default build (and the
    adapter never materializes more than chunk_rows rows per fetch)."""
    _, _, nvprof, _, root = trio
    out = str(root / "store_chunked")
    run_generation(nvprof, out, n_ranks=2,
                   cfg=GenerationConfig(chunk_rows=7))
    _assert_stores_bit_identical(native_store, out)


# --- ingest-time predicate pushdown -----------------------------------------

def test_pushdown_matches_posthoc_filter_oracle(trio, native_store):
    """A store built with the predicates pushed into the SQLite reads
    answers the same Query bit-identically to the full store (the
    analysis-time row masks re-apply the predicates), while provably
    reading fewer rows: ingest_rows_skipped > 0 on the caller's store
    instance."""
    _, _, nvprof, _, root = trio
    man = TraceStore(native_store).read_manifest()
    lo, hi = man.t_start, man.t_end
    q = Query(metrics=("k_stall",),
              time_window=(lo + (hi - lo) // 4, lo + (hi - lo) // 2),
              kernel_names=tuple(range(8)))

    out = str(root / "store_pushdown")
    store = TraceStore(out)
    rep = run_generation(nvprof, out, n_ranks=2,
                         cfg=GenerationConfig(pushdown=q), store=store)
    assert rep.ingest_rows_skipped > 0
    assert store.io_counts["ingest_rows_skipped"] == rep.ingest_rows_skipped
    assert store.io_counts["ingest_rows_read"] == rep.ingest_rows_read
    # the predicate is recorded so appends re-apply it
    man_sel = TraceStore(out).read_manifest()
    assert man_sel.extra["ingest_pushdown"] == q.to_spec()
    # same shard plan as the full store (boundaries are unfiltered)
    assert (man_sel.t_start, man_sel.t_end, man_sel.n_shards) == \
        (man.t_start, man.t_end, man.n_shards)

    a = run_aggregation(native_store, query=q)
    b = run_aggregation(out, query=q)
    for f in ("count", "sum", "sumsq", "min", "max"):
        np.testing.assert_array_equal(getattr(a.stats, f),
                                      getattr(b.stats, f))


def test_ranks_pushdown_skips_whole_sources(trio):
    """``ranks`` pushdown never opens the excluded source DB's event
    tables: everything it held in range lands in ingest_rows_skipped."""
    _, _, nvprof, _, root = trio
    full = SqliteTraceSource.open(nvprof[1])
    in_range = full.count_range()
    out = str(root / "store_ranks")
    store = TraceStore(out)
    rep = run_generation(nvprof, out, n_ranks=1,
                         cfg=GenerationConfig(pushdown=Query(ranks=(0,))),
                         store=store)
    assert rep.ingest_rows_skipped == in_range
    man = TraceStore(out).read_manifest()
    # src_rank 1 contributed no rows at all
    st = TraceStore(out)
    for s in range(man.n_shards):
        cols = st.read_shard(s)
        assert not np.any(cols["src_rank"] == 1.0)


def test_append_reapplies_recorded_pushdown(trio, tmp_path):
    """Appending to a selective store re-applies ITS manifest predicate
    (cfg is ignored), so the store stays coherent for its query."""
    from repro.core import run_append
    ds, _, _, _, _ = trio
    t0 = int(ds.traces[0].kernels.start.min())
    cutoff = (t0 // _NS) * _NS + 8 * _NS
    paths = [str(tmp_path / f"rank{tr.rank}.sqlite") for tr in ds.traces]
    for tr, p in zip(ds.traces, paths):
        write_nvprof_rank_db(p, truncate_trace(tr, cutoff))
    q = Query(kernel_names=tuple(range(8)))
    out = str(tmp_path / "store")
    run_generation(paths, out, n_ranks=2, cfg=GenerationConfig(pushdown=q))
    for tr, p in zip(ds.traces, paths):
        append_fixture_rank_db(p, trace_remainder(tr, cutoff),
                               flavor="nvprof")
    store = TraceStore(out)
    run_append(paths, out, store=store)
    assert store.io_counts["ingest_rows_skipped"] > 0
    # every kernel row in the store honors the predicate
    man = store.read_manifest()
    assert man.extra["ingest_pushdown"] == q.to_spec()
    for s in range(man.n_shards):
        names = store.read_shard(s)["k_name"]
        assert names.size == 0 or names.max() < 8


# --- name-table spelling tolerance ------------------------------------------

def test_read_kernel_names_tolerates_both_spellings(trio):
    _, native, nvprof, nsys, _ = trio
    for p in (native[0], nvprof[0], nsys[0]):
        names = read_kernel_names(p)
        assert len(names) == 64
        assert all(isinstance(v, str) and v for v in names.values())
    assert read_kernel_names(native[0]) == read_kernel_names(nvprof[0])


@pytest.mark.parametrize("flavor", ["nvprof", "nsys"])
def test_missing_name_rows_fall_back_to_kernel_id(trio, tmp_path, flavor):
    """A lossy export missing string-table rows for referenced ids must
    ingest with ``kernel_{id}`` placeholders, never KeyError."""
    ds, _, _, _, _ = trio
    writer = (write_nvprof_rank_db if flavor == "nvprof"
              else write_nsys_rank_db)
    p = str(tmp_path / f"lossy_{flavor}.sqlite")
    writer(p, ds.traces[0], drop_name_ids=(3, 5))
    names = SqliteTraceSource.open(p).kernel_names()
    assert names[3] == "kernel_3" and names[5] == "kernel_5"
    assert names[0] != "kernel_0"          # intact ids keep real names
    out = str(tmp_path / f"store_{flavor}")
    run_generation([p], out, n_ranks=1)
    man = TraceStore(out).read_manifest()
    assert man.extra["kernel_names"]["3"] == "kernel_3"


def _renamed(tr, ids_where, new_id):
    """``tr`` with the kernels ``ids_where`` selects renamed ``new_id``."""
    ids = np.where(ids_where, new_id, tr.kernels.name_id).astype(np.int32)
    return RankTrace(rank=tr.rank,
                     kernels=dataclasses.replace(tr.kernels, name_id=ids),
                     memcpys=tr.memcpys, gpus=tr.gpus,
                     names={**tr.names, new_id: f"late_{new_id}"})


def test_read_names_are_bounded_to_its_window(trio, tmp_path):
    """``read().names``: the whole string table (as ``kernel_names()``
    has it), but ``kernel_{id}`` fallbacks only for ids of the kernel
    rows in the read's window — with or without a pushdown, which never
    changes the set."""
    ds = trio[0]
    tr = ds.traces[0]
    half = len(tr.kernels) // 2
    # id 5 only in the first half of the rows: outside the window below
    tr = _renamed(tr, (np.arange(len(tr.kernels)) >= half)
                  & (tr.kernels.name_id == 5), 6)
    assert 3 in tr.kernels.name_id[half:]
    p = str(tmp_path / "lossy.sqlite")
    write_nsys_rank_db(p, tr, drop_name_ids=(3, 5))
    src = SqliteTraceSource.open(p)
    full = src.kernel_names()
    assert full[3] == "kernel_3" and full[5] == "kernel_5"
    strings = {i: n for i, n in full.items() if i not in (3, 5)}

    assert src.read(rank=0).names == full
    for pushdown in (None, Query(kernel_names=(0, 1))):
        names = src.read(rank=0, min_rowids=(half, 0),
                         pushdown=pushdown).names
        assert names[3] == "kernel_3"       # referenced in the window
        assert 5 not in names               # referenced only before it
        assert {i: n for i, n in names.items() if i != 3} == strings
        assert src.window_names(min_rowids=(half, 0)) == (
            names, len(tr.kernels) - half)


_LATE_ID = 4242

# case -> (flavor, recorded pushdown, ids dropped from the string table,
#          whether only the last rank's export drops them)
_APPEND_NAME_CASES = {
    "native": ("native", None, (), False),
    "nvprof": ("nvprof", None, (), False),
    "nsys": ("nsys", None, (), False),
    "lossy": ("nsys", None, (3, _LATE_ID), False),
    "lossy_last_rank": ("nsys", None, (3, _LATE_ID), True),
    "kernel_names_pushdown": (
        "nvprof", Query(kernel_names=tuple(range(8))), (3, _LATE_ID), False),
    "ranks_pushdown": ("nvprof", Query(ranks=(0,)), (3, _LATE_ID), False),
}


@pytest.mark.parametrize("case", sorted(_APPEND_NAME_CASES))
def test_appended_kernel_names_match_cold_generation(trio, tmp_path, case):
    """After two appends, the manifest's ``kernel_names`` equals a cold
    generation's over the grown DBs, though the append reads names only
    for the rows it ingests. The last rank's second append brings an id
    no earlier row uses (lossy cases drop it from the string table, and
    the pushdown cases keep its rows out of the reads). Where only one
    export lacks a name, the other's spelling wins over the fallback."""
    from repro.core import run_append
    ds = trio[0]
    flavor, pushdown, drop_ids, last_only = _APPEND_NAME_CASES[case]
    drops = [() if last_only and tr.rank != ds.traces[-1].rank
             else drop_ids for tr in ds.traces]
    t0 = (int(ds.traces[0].kernels.start.min()) // _NS) * _NS
    c1, c2 = t0 + 8 * _NS, t0 + 12 * _NS
    traces = list(ds.traces)
    last = traces[-1]
    traces[-1] = _renamed(last, (last.kernels.end > c2)
                          & (np.arange(len(last.kernels)) % 7 == 0),
                          _LATE_ID)
    paths = [str(tmp_path / f"rank{tr.rank}.sqlite") for tr in traces]
    for tr, p, drop in zip(traces, paths, drops):
        if flavor == "native":
            write_rank_db(p, truncate_trace(tr, c1))
        else:
            writer = (write_nvprof_rank_db if flavor == "nvprof"
                      else write_nsys_rank_db)
            writer(p, truncate_trace(tr, c1), drop_name_ids=drop)
    cfg = GenerationConfig(pushdown=pushdown)
    out = str(tmp_path / "store")
    run_generation(paths, out, n_ranks=2, cfg=cfg)
    for part in (lambda tr: truncate_trace(trace_remainder(tr, c1), c2),
                 lambda tr: trace_remainder(tr, c2)):
        for tr, p, drop in zip(traces, paths, drops):
            if flavor == "native":
                append_rank_db(p, part(tr))
            else:
                append_fixture_rank_db(p, part(tr), flavor=flavor,
                                       drop_name_ids=drop)
        run_append(paths, out)
    cold = str(tmp_path / "cold")
    run_generation(paths, cold, n_ranks=2, cfg=cfg)
    got = TraceStore(out).read_manifest().extra["kernel_names"]
    want = TraceStore(cold).read_manifest().extra["kernel_names"]
    assert got == want
    assert want[str(_LATE_ID)] == (
        "kernel_4242" if _LATE_ID in drop_ids else "late_4242")
    assert (want["3"] == "kernel_3") == (3 in drop_ids and not last_only)


def test_rowid_watermark_dialect_aware(trio):
    _, native, nvprof, nsys, _ = trio
    wms = {rowid_watermark(p[0]) for p in (native, nvprof, nsys)}
    assert len(wms) == 1                    # identical data, same rowids
    assert next(iter(wms)) > (0, 0)


# --- streaming tail of a live-written Nsight export -------------------------

def test_streaming_tail_of_live_nsys_export(tmp_path):
    """The streaming plane tails a GROWING Nsight-schema export by rowid
    watermark: growth is detected, one ingest tick appends exactly the
    new rows (duplicate- and loss-free), and the final store answers
    the reducer suite bit-identically to a cold rebuild of the full
    export."""
    from repro.serve import IngestConfig, QueryService, ServiceConfig
    ds = generate_synthetic(SyntheticSpec(
        n_ranks=2, kernels_per_rank=3000, memcpys_per_rank=400,
        duration_s=16.0, n_anomaly_windows=2, seed=13))
    t0 = int(ds.traces[0].kernels.start.min())
    cutoff = (t0 // _NS) * _NS + 8 * _NS
    paths = [str(tmp_path / f"rank{tr.rank}.nsys-rep.sqlite")
             for tr in ds.traces]
    for tr, p in zip(ds.traces, paths):
        write_nsys_rank_db(p, truncate_trace(tr, cutoff))
    store_dir = str(tmp_path / "store")
    run_generation(paths, store_dir, n_ranks=2)

    svc = QueryService(store_dir, ServiceConfig(tick_ms=1.0))
    ing = svc.ensure_ingestor(IngestConfig())
    ing.attach(paths)
    assert ing.poll_once() == []            # snapshot fully covered
    for tr, p in zip(ds.traces, paths):
        append_fixture_rank_db(p, trace_remainder(tr, cutoff),
                               flavor="nsys")
    assert sorted(ing.poll_once()) == sorted(ing.attached())
    p = ing.submit(t_detect=time.monotonic())
    assert svc.drain_once(block_s=0.0) == 1
    assert p.error is None
    assert p.tick_info["ingest"]["rows_ingested"] > 0
    assert ing.poll_once() == []            # caught up, no re-detection

    cold = str(tmp_path / "cold")
    run_generation(paths, cold, n_ranks=2)
    a = run_aggregation(store_dir, query=SUITE_QUERY)
    b = run_aggregation(cold, query=SUITE_QUERY)
    for f in ("count", "sum", "sumsq", "min", "max"):
        np.testing.assert_array_equal(getattr(a.grouped, f),
                                      getattr(b.grouped, f))
    np.testing.assert_array_equal(a.reduced["quantile"].counts,
                                  b.reduced["quantile"].counts)


# --- diff engine over two ingested real traces ------------------------------

def test_diff_of_two_ingested_traces(tmp_path):
    """The trace-diff engine runs against two stores built from real
    profiler exports: a respecialized clean pair passes, an injected
    slowdown regresses."""
    common = dict(n_ranks=2, kernels_per_rank=3000, memcpys_per_rank=300,
                  duration_s=12.0, seed=7)
    ds_a = generate_synthetic(SyntheticSpec(**common, name_variant=0))
    ds_b = generate_synthetic(SyntheticSpec(**common, name_variant=1))
    ds_c = inject_slowdown(ds_b, 1.6, (3, 24, 45))
    stores = {}
    for tag, ds in (("a", ds_a), ("b", ds_b), ("c", ds_c)):
        dbs = write_fixture_dbs(ds, str(tmp_path / f"dbs_{tag}"),
                                flavor="nsys")
        out = str(tmp_path / f"store_{tag}")
        run_generation(dbs, out, n_ranks=2)
        stores[tag] = out
    pipe = VariabilityPipeline(PipelineConfig(n_ranks=2, backend="serial"))
    clean = pipe.diff(stores["a"], stores["b"])
    assert clean.verdict != "regressed"
    bad = pipe.diff(stores["a"], stores["c"])
    assert bad.verdict == "regressed"
