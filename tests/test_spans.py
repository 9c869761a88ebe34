"""Program spans (``repro.core.spans``): the in-process totals, the
profiler events a served query writes, ``/v1/stats`` → ``"spans"``, and
a store build that stays free of JAX."""

import glob
import json
import os
import shutil
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from repro.core import (Query, append_rank_db, run_append,
                        run_generation, trace_remainder, truncate_trace,
                        write_rank_db)
from repro.core.events import SyntheticSpec, generate_synthetic
from repro.core.spans import TOTALS, span
from repro.serve.query_service import QueryService, ServiceConfig
from repro.serve.stream import IngestConfig

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


@pytest.fixture(scope="module")
def store_dir(tmp_path_factory):
    ds = generate_synthetic(SyntheticSpec(
        n_ranks=2, kernels_per_rank=1500, memcpys_per_rank=200,
        duration_s=8.0, seed=11))
    root = tmp_path_factory.mktemp("spans")
    paths = []
    for tr in ds.traces:
        p = str(root / f"rank{tr.rank}.sqlite")
        write_rank_db(p, tr)
        paths.append(p)
    out = str(root / "store")
    run_generation(paths, out, n_ranks=2)
    return out


def test_totals_nesting_self_time_and_stat_sums():
    """Self time leaves out the nested span on the same thread; a span
    on another thread is no child; numeric stats sum, the ``tick``
    identifier and string stats do not."""
    names = ("test.spans.outer", "test.spans.inner", "test.spans.other")
    before = TOTALS.snapshot()

    def other():
        with span(names[2]):
            time.sleep(0.03)

    for _ in range(2):
        with span(names[0], tick=7, rows=10, kind="query") as sp:
            time.sleep(0.02)
            t = threading.Thread(target=other)
            t.start()
            with span(names[1], bytes=100):
                time.sleep(0.03)
            t.join(timeout=10)
            assert not t.is_alive()
            sp.set(rows_kept=4)
    got = TOTALS.snapshot()
    for n in names:
        assert before.get(n, {"count": 0})["count"] == 0
    outer, inner, oth = (got[n] for n in names)
    assert outer["count"] == inner["count"] == oth["count"] == 2
    assert outer["stats"] == {"rows": 20, "rows_kept": 8}
    assert inner["stats"] == {"bytes": 200}
    assert inner["self_ms"] == inner["total_ms"] >= 60
    # the outer span's own time is its 20 ms sleeps (and thread starts):
    # the inner span's 60 ms is left out, the other thread's is not
    assert outer["total_ms"] >= outer["self_ms"] + inner["total_ms"] - 1e-6
    assert 40 <= outer["self_ms"] < outer["total_ms"] - 50
    assert oth["self_ms"] >= 60


def test_store_build_imports_no_jax(tmp_path):
    """A store build through the pipeline's ``process`` backend, and a
    shard read after it, record spans without importing JAX."""
    code = f"""
import sys
sys.path.insert(0, {SRC!r})
from repro.core import (PipelineConfig, SyntheticSpec, TraceStore,
                        VariabilityPipeline, generate_synthetic,
                        write_synthetic_dbs)
from repro.core.spans import TOTALS
ds = generate_synthetic(SyntheticSpec(
    n_ranks=2, kernels_per_rank=1500, memcpys_per_rank=200,
    duration_s=8.0, seed=5))
paths = write_synthetic_dbs(ds, {str(tmp_path / "dbs")!r})
pipe = VariabilityPipeline(PipelineConfig(n_ranks=2, backend="process"))
pipe.generate(paths, {str(tmp_path / "store")!r})
TraceStore({str(tmp_path / "store")!r}).read_shard(0)
assert TOTALS.snapshot()["repro.shard.read"]["stats"]["rows"] > 0
print(sorted(m for m in sys.modules if m == "jax" or m.startswith("jax.")))
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1] == "[]"


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        out.append((ev.name, dict(ev.stats)))
    return out


def test_served_query_spans_in_the_profiler_trace(store_dir, tmp_path):
    """A cold query served on the jax backend under a profiler session
    leaves its tick, shard-read and device-dispatch spans in the trace,
    with their stats."""
    import jax

    svc = QueryService(store_dir, ServiceConfig(backend="jax",
                                                tick_ms=1.0))
    svc.start(serve_http=False)
    log_dir = str(tmp_path / "trace")
    try:
        jax.profiler.start_trace(log_dir)
        try:
            p = svc.submit([_query()])
            assert p.done.wait(120) and p.error is None
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.stop()
    events = _host_events(log_dir)
    ticks = [st for n, st in events if n == "repro.tick.exec"]
    assert ticks
    assert all(st["kind"] == "query" and st["tick"] >= 1 for st in ticks)
    assert ticks[0]["requests"] == 1 and ticks[0]["queued_ns"] >= 0
    reads = [st for n, st in events if n == "repro.shard.read"]
    assert reads and all(st["rows"] >= 0 for st in reads)
    dispatch = [st for n, st in events if n == "repro.reduce.dispatch"]
    assert dispatch and dispatch[0]["reducer"] == "moments"
    assert dispatch[0]["devices"] == dispatch[0]["metrics"] == 1
    assert dispatch[0]["rows_padded"] >= 1 and dispatch[0]["n_seg"] >= 1
    h2d = [st for n, st in events if n == "repro.reduce.h2d"]
    assert h2d and h2d[0]["bytes"] > 0


def _query():
    from repro.core import Query
    return Query(metrics=("k_stall",), group_by="k_device")


def test_stats_route_carries_the_span_totals(store_dir, tmp_path):
    """``GET /v1/stats`` serves the totals: after one served query, the
    tick, commit and shard-read spans with their counts and sums."""
    svc = QueryService(store_dir, ServiceConfig(tick_ms=1.0, port=0))
    svc.start(serve_http=True)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{svc.cfg.port}/v1/query",
            data=json.dumps([{"metrics": ["m_duration"],
                              "group_by": "m_kind"}]).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
        with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.cfg.port}/v1/stats",
                timeout=60) as r:
            stats = json.loads(r.read())
    finally:
        svc.stop()
    spans = stats["spans"]
    for name in ("repro.tick.exec", "repro.tick.lanes", "repro.commit",
                 "repro.commit.evict", "repro.render", "repro.shard.read",
                 "repro.scan.prep", "repro.merge", "repro.respond"):
        assert spans[name]["count"] >= 1, name
        assert spans[name]["total_ms"] >= spans[name]["self_ms"] >= 0
    assert spans["repro.tick.exec"]["stats"]["requests"] >= 1
    assert "tick" not in spans["repro.tick.exec"]["stats"]
    assert spans["repro.shard.read"]["stats"]["rows"] > 0


def test_summary_hit_ticks_make_no_syscall_in_eviction(store_dir,
                                                       tmp_path):
    """Under the default summary budget, the eviction step of a tick
    that only hits the summary cache neither lists nor stats the store:
    ``repro.commit.evict`` counts one stat call, for the one summary
    the first (cold) ask wrote, and no listing; ``/v1/stats`` counts no
    summary rescan. The ledger then holds the store's summary bytes."""
    store = shutil.copytree(store_dir, str(tmp_path / "store"))
    svc = QueryService(store, ServiceConfig(tick_ms=1.0, port=0))
    svc.start(serve_http=True)
    before = TOTALS.snapshot().get("repro.commit.evict",
                                   {"count": 0, "stats": {}})
    try:
        for _ in range(4):
            p = svc.submit([Query(metrics=("m_bytes",),
                                  group_by="src_rank")])
            assert p.done.wait(120) and p.error is None
        assert p.results[0]["cache_hit"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.cfg.port}/v1/stats",
                timeout=60) as r:
            stats = json.loads(r.read())
    finally:
        svc.stop()
    got = stats["spans"]["repro.commit.evict"]
    assert got["count"] - before["count"] == 4
    assert got["stats"]["rescan"] - before["stats"].get("rescan", 0) == 0
    assert (got["stats"]["stat_calls"]
            - before["stats"].get("stat_calls", 0)) == 1
    assert stats["summary_rescans"] == 0 and stats["evictions"] == 0
    on_disk = {k: os.path.getsize(os.path.join(store, f"summary_{k}.npz"))
               for k in svc.store.summary_keys()}
    assert svc.cache._sizes == on_disk


def test_ingest_tick_spans_its_append_phases(tmp_path):
    """One ingest tick records each phase of its append (a read and a
    join per rank DB, one stage, one commit), its fence lanes and the
    fence publication."""
    ds = generate_synthetic(SyntheticSpec(
        n_ranks=2, kernels_per_rank=1500, memcpys_per_rank=200,
        duration_s=8.0, seed=13))
    t0 = int(ds.traces[0].kernels.start.min())
    cutoff = t0 + 4 * 10**9
    paths = []
    for tr in ds.traces:
        p = str(tmp_path / f"rank{tr.rank}.sqlite")
        write_rank_db(p, truncate_trace(tr, cutoff))
        paths.append(p)
    store = str(tmp_path / "store")
    run_generation(paths, store, n_ranks=2)
    svc = QueryService(store, ServiceConfig(tick_ms=1.0))
    ing = svc.ensure_ingestor(IngestConfig())
    ing.attach(paths)
    for tr, p in zip(ds.traces, paths):
        append_rank_db(p, trace_remainder(tr, cutoff))
    before = TOTALS.snapshot()
    pending = ing.submit(t_detect=time.monotonic())
    assert svc.drain_once(block_s=0.0) == 1 and pending.error is None
    rows = pending.tick_info["ingest"]["rows_ingested"]
    assert rows > 0
    got = TOTALS.snapshot()

    def delta(name, key="count"):
        return got[name][key] - before.get(name, {key: 0})[key]

    assert delta("repro.append.read") == len(paths)
    assert delta("repro.append.join") == len(paths)
    assert delta("repro.append.stage") == delta("repro.append.commit") == 1
    assert (got["repro.append.join"]["stats"]["rows"]
            - before.get("repro.append.join", {"stats": {"rows": 0}})
            ["stats"]["rows"]) == rows
    for name in ("repro.tick.exec", "repro.tick.lanes", "repro.commit",
                 "repro.fence.publish"):
        assert delta(name) == 1, name


def test_append_name_refresh_reads_only_the_new_rows(tmp_path):
    """The kernel-name refresh of an append draws its fallback ids from
    the N kernel rows it ingests, not from the M + N the DB then holds:
    the ``name_rows`` stat of ``repro.append.read`` is N."""
    ds = generate_synthetic(SyntheticSpec(
        n_ranks=1, kernels_per_rank=3000, memcpys_per_rank=200,
        duration_s=8.0, seed=17))
    tr = ds.traces[0]
    cutoff = int(tr.kernels.end.max()) - 2 * 10**8
    p = str(tmp_path / "rank0.sqlite")
    write_rank_db(p, truncate_trace(tr, cutoff))
    store = str(tmp_path / "store")
    run_generation([p], store, n_ranks=1)
    tail = trace_remainder(tr, cutoff)
    n, m = len(tail.kernels), len(tr.kernels) - len(tail.kernels)
    assert 0 < 10 * n < m
    append_rank_db(p, tail)
    before = TOTALS.snapshot().get("repro.append.read",
                                   {"count": 0, "stats": {}})
    run_append([p], store)
    got = TOTALS.snapshot()["repro.append.read"]
    assert got["count"] - before["count"] == 1
    assert (got["stats"]["name_rows"]
            - before["stats"].get("name_rows", 0)) == n
