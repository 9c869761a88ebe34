"""Streaming ingest plane + v1 API tests: live-writer tailing with no
duplicated and no lost rows, crash-interrupted ingest ticks rolling
FORWARD from the intent journal (never double-ingesting), fence-event
push over the v1 long-poll endpoint, full v1 route coverage with the
shared error envelope, legacy aliases answering with a ``Deprecation``
header, and the legacy metric-arg spellings warning while minting
bit-identical cache keys."""

import os
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core import (PipelineConfig, Query, SyntheticSpec, TraceStore,
                        VariabilityPipeline, append_rank_db,
                        generate_synthetic, run_aggregation,
                        run_generation, trace_remainder, truncate_trace,
                        write_rank_db)
from repro.serve import (IngestConfig, QueryClient, QueryService,
                         ServiceConfig, ServiceError)

_NS = 1_000_000_000
SUITE_QUERY = Query(metrics=("k_stall", "m_duration"), group_by="src_rank",
                    reducers=("moments", "quantile"))


@pytest.fixture(scope="module")
def growing(tmp_path_factory):
    """A live profiler run: snapshots at 12 s, the rest arriving later
    in batches at the same DB paths (fresh larger rowids)."""
    spec = SyntheticSpec(n_ranks=2, kernels_per_rank=4000,
                         memcpys_per_rank=500, duration_s=24.0,
                         n_anomaly_windows=2, seed=7)
    ds = generate_synthetic(spec)
    t0 = int(ds.traces[0].kernels.start.min())
    cutoff = (t0 // _NS) * _NS + 12 * _NS
    return ds, cutoff


def _snapshot_store(ds, cutoff, root):
    db_dir = os.path.join(str(root), "dbs")
    os.makedirs(db_dir, exist_ok=True)
    paths = [os.path.join(db_dir, f"rank{tr.rank}.sqlite")
             for tr in ds.traces]
    for tr, p in zip(ds.traces, paths):
        write_rank_db(p, truncate_trace(tr, cutoff))
    store_dir = os.path.join(str(root), "store")
    run_generation(paths, store_dir, n_ranks=2)
    return paths, store_dir


def _grow(ds, paths, cutoff):
    for tr, p in zip(ds.traces, paths):
        append_rank_db(p, trace_remainder(tr, cutoff))


def _assert_identical_to_cold_rebuild(store_dir, paths, root):
    """The streamed store answers the full reducer suite bit-identically
    to a cold ``run_generation`` from the final DBs."""
    cold = os.path.join(str(root), "cold")
    run_generation(paths, cold, n_ranks=2)
    a = run_aggregation(store_dir, query=SUITE_QUERY)
    b = run_aggregation(cold, query=SUITE_QUERY)
    for f in ("count", "sum", "sumsq", "min", "max"):
        np.testing.assert_array_equal(getattr(a.grouped, f),
                                      getattr(b.grouped, f))
    np.testing.assert_array_equal(a.group_keys, b.group_keys)
    np.testing.assert_array_equal(a.reduced["quantile"].counts,
                                  b.reduced["quantile"].counts)


# --- deterministic ingest ticks (no threads: submit + drain_once) ----------

def test_ingest_tick_rides_pipeline_and_diffs_fences(growing, tmp_path):
    """One ingest tick through the admission -> exec -> commit pipeline:
    append provenance lands on the pending, the fence queries run as
    owned lanes of the SAME tick, a fence event is published, and a
    second tick with no growth publishes nothing new."""
    ds, cutoff = growing
    paths, store_dir = _snapshot_store(ds, cutoff, tmp_path)
    svc = QueryService(store_dir, ServiceConfig(tick_ms=1.0))
    ing = svc.ensure_ingestor(IngestConfig())
    assert ing.attach(paths) == [os.path.abspath(p) for p in paths]
    # resumed watermarks: the manifest already covers the snapshot rows
    assert all(w > (0, 0) for w in ing.watermarks().values())
    assert ing.poll_once() == []            # no growth yet

    _grow(ds, paths, cutoff)
    assert sorted(ing.poll_once()) == sorted(ing.attached())
    p = ing.submit(t_detect=time.monotonic())
    assert svc.drain_once(block_s=0.0) == 1
    assert p.error is None
    info = p.tick_info["ingest"]
    assert p.tick_info["kind"] == "ingest"
    assert info["rows_ingested"] > 0
    assert info["dirty_shards"] or info["n_new_shards"]
    assert info["event_to_fence_ms"] > 0.0
    # the tick's commit published to the hub and advanced watermarks
    events = ing.hub.events_since(0)
    assert len(events) == 1 and events[0]["kind"] in ("fence", "ingest")
    assert events[0]["ingest"]["rows_ingested"] == info["rows_ingested"]
    assert ing.poll_once() == []            # fully caught up

    # no growth -> submit ingests zero rows and publishes nothing
    p2 = ing.submit(t_detect=time.monotonic())
    assert svc.drain_once(block_s=0.0) == 1
    assert p2.error is None
    assert p2.tick_info["ingest"]["rows_ingested"] == 0
    assert ing.hub.events_since(events[0]["seq"]) == []

    st = ing.stats()
    assert st["ingest_ticks"] == 2
    assert st["rows_ingested"] == info["rows_ingested"]
    assert st["event_to_fence_p99_ms"] > 0.0
    _assert_identical_to_cold_rebuild(store_dir, paths, tmp_path)


def test_interrupted_ingest_tick_recovers_via_journal(growing, tmp_path,
                                                      monkeypatch):
    """A tick crashing mid-commit (after the intent journal, some staged
    shards published, some not) fails THAT tick only; the next tick
    rolls the journal FORWARD and re-reads zero rows — the journaled
    watermarks already cover the ingested batch, so nothing is
    double-ingested and the store ends bit-identical to a cold
    rebuild."""
    ds, cutoff = growing
    paths, store_dir = _snapshot_store(ds, cutoff, tmp_path)
    svc = QueryService(store_dir, ServiceConfig(tick_ms=1.0))
    ing = svc.ensure_ingestor(IngestConfig())
    ing.attach(paths)
    _grow(ds, paths, cutoff)

    real = TraceStore.commit_staged_shard
    calls = {"n": 0}

    def crashing_commit(self, idx):
        calls["n"] += 1
        if calls["n"] > 1:
            raise RuntimeError("injected crash mid-commit")
        return real(self, idx)

    monkeypatch.setattr(TraceStore, "commit_staged_shard",
                        crashing_commit)
    p = ing.submit(t_detect=time.monotonic())
    assert svc.drain_once(block_s=0.0) == 1
    assert p.error is not None
    assert p.error[0] == 500 and p.error[1] == "ingest_failed"
    assert "injected crash" in p.error[2]
    assert calls["n"] > 1                   # crashed mid-commit…
    intent = os.path.join(store_dir, "append_intent.json")
    assert os.path.exists(intent)           # …journal survives the tick
    assert ing.stats()["errors"] == 1

    monkeypatch.setattr(TraceStore, "commit_staged_shard", real)
    p2 = ing.submit(t_detect=time.monotonic())
    assert svc.drain_once(block_s=0.0) == 1
    assert p2.error is None
    info = p2.tick_info["ingest"]
    assert info["recovered"] is True
    assert info["rows_ingested"] == 0       # rolled forward, not re-read
    assert not os.path.exists(intent)
    st = ing.stats()
    assert st["recoveries"] == 1
    assert ing.poll_once() == []
    _assert_identical_to_cold_rebuild(store_dir, paths, tmp_path)


def test_live_writer_mid_tail_no_duplicate_no_lost_rows(growing,
                                                        tmp_path):
    """Writers keep appending batches WHILE the tailer polls and ingest
    ticks execute — rows landing mid-append stay above the dispatched
    watermark and ride a later tick. After quiesce the streamed store
    is bit-identical to a cold rebuild of the final DBs: any duplicated
    or lost row would break the count equality."""
    ds, cutoff = growing
    paths, store_dir = _snapshot_store(ds, cutoff, tmp_path)
    svc = QueryService(store_dir, ServiceConfig(
        tick_ms=1.0, ingest=IngestConfig(poll_ms=5.0)))
    ing = svc.ensure_ingestor()
    ing.attach(paths)
    svc.start(serve_http=False)
    try:
        cuts = [cutoff + k * 3 * _NS for k in range(1, 4)] + [None]

        def writer():
            lo = cutoff
            for hi in cuts:
                for tr, p in zip(ds.traces, paths):
                    batch = (trace_remainder(tr, lo) if hi is None else
                             trace_remainder(truncate_trace(tr, hi), lo))
                    append_rank_db(p, batch)
                lo = hi
                time.sleep(0.02)        # overlap writes with ingests

        threads = [threading.Thread(target=writer)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert ing.quiesce(timeout_s=60.0)
        st = ing.stats()
        assert st["errors"] == 0
        assert st["ingest_ticks"] >= 1
    finally:
        svc.stop()
    _assert_identical_to_cold_rebuild(store_dir, paths, tmp_path)


# --- the v1 HTTP surface ---------------------------------------------------

def _raw_get(port, path):
    import json as _json
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return r.status, dict(r.headers), _json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), _json.loads(e.read())


def test_v1_routes_envelope_and_legacy_deprecation(growing, tmp_path):
    """Every v1 endpoint answers; every error speaks the shared
    envelope; the legacy unversioned aliases answer identically but
    stamped ``Deprecation: true`` with a successor-version ``Link``."""
    ds, cutoff = growing
    paths, store_dir = _snapshot_store(ds, cutoff, tmp_path)
    svc = QueryService(store_dir,
                       ServiceConfig(tick_ms=2.0, port=0)).start()
    c = QueryClient(port=svc.cfg.port)
    try:
        assert c.wait_healthy(timeout_s=10.0)
        assert c.healthz()["api"] == "v1"
        assert c.stats()["ingest"] is None

        r = c.query(Query(metrics=("k_stall",), group_by="m_kind"))
        assert r["n_samples"] > 0

        # legacy aliases: same answers, Deprecation + Link headers
        for path in ("/healthz", "/stats"):
            status, hdr, _ = _raw_get(svc.cfg.port, path)
            assert status == 200
            assert hdr.get("Deprecation") == "true"
            assert "successor-version" in hdr.get("Link", "")
        status, hdr, _ = _raw_get(svc.cfg.port, "/v1/healthz")
        assert status == 200 and "Deprecation" not in hdr

        # the shared error envelope, across routes and codes
        status, _, body = _raw_get(svc.cfg.port, "/v1/nope")
        assert status == 404
        assert body["error"]["code"] == "not_found"
        with pytest.raises(ServiceError) as ei:
            c.fences(since=0, timeout_s=0.2)
        assert ei.value.status == 409
        assert ei.value.code == "no_ingest_plane"
        with pytest.raises(ServiceError) as ei:
            c.attach([])                # malformed body
        assert ei.value.status == 400
        assert ei.value.code == "bad_request"
        with pytest.raises(ServiceError) as ei:
            c.query({"metrics": ["k_stall"], "interval_ns": "bogus"})
        assert ei.value.code == "bad_request"
    finally:
        svc.stop()


def test_fence_push_received_over_http(growing, tmp_path):
    """The facade round trip: ``VariabilityPipeline.stream`` serves a
    store already tailing its rank DBs; a live write produces a fence
    event a long-polling ``QueryClient`` receives, ingest provenance
    shows up under /v1/stats, and detach stops the tailing."""
    ds, cutoff = growing
    paths, store_dir = _snapshot_store(ds, cutoff, tmp_path)
    pipe = VariabilityPipeline(PipelineConfig(n_ranks=2))
    svc = pipe.stream(store_dir, paths,
                      ingest=IngestConfig(poll_ms=5.0), tick_ms=2.0)
    c = QueryClient(port=svc.cfg.port)
    try:
        assert c.wait_healthy(timeout_s=10.0)
        assert c.healthz()["ingest"] is True
        _grow(ds, paths, cutoff)
        body = c.fences(since=0, timeout_s=30.0)
        assert body["events"], "no fence event within the long poll"
        e = body["events"][0]
        assert e["kind"] in ("fence", "ingest")
        assert e["ingest"]["rows_ingested"] > 0
        assert body["next_since"] >= e["seq"]
        # caught up: once every written row is in, a fresh long poll
        # past the last event is empty (the two rank DBs' writes may
        # land in two ticks, so the rest is drained first)
        assert svc.ingestor.quiesce(timeout_s=60.0)
        rest = c.fences(since=body["next_since"], timeout_s=0.2)
        again = c.fences(since=rest["next_since"], timeout_s=0.2)
        assert again["events"] == []
        st = c.stats()["ingest"]
        assert st["rows_ingested"] > 0
        assert st["event_to_fence_p99_ms"] > 0.0
        assert st["errors"] == 0
        out = c.detach(paths)
        assert out["tailing"] == []
    finally:
        svc.stop()
        pipe.close()
    _assert_identical_to_cold_rebuild(store_dir, paths, tmp_path)


# --- legacy argument spellings: warn, but mint identical keys --------------

def test_legacy_metric_args_warn_and_mint_identical_cache_keys(tmp_path):
    """The migration contract: old-style (metrics, group_by, reducers)
    arguments emit DeprecationWarning but produce byte-identical
    summary AND partial keys to the Query spelling — warm caches stay
    warm across the API migration."""
    store = TraceStore(str(tmp_path))
    q = Query(metrics=("k_stall",), group_by="m_kind",
              reducers=("moments", "quantile"))
    pk = (0, 10, 10)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        legacy_s = store.summary_key(pk, metrics=["k_stall"],
                                     group_by="m_kind",
                                     reducers=("moments", "quantile"))
    with pytest.warns(DeprecationWarning, match="deprecated"):
        legacy_p = store.partial_key(pk, metrics=["k_stall"],
                                     group_by="m_kind",
                                     reducers=("moments", "quantile"))
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # Query spelling: no warning
        assert store.summary_key(pk, query=q) == legacy_s
        assert store.partial_key(pk, query=q) == legacy_p


def test_legacy_run_aggregation_args_warn_and_match_query(growing,
                                                          tmp_path):
    ds, cutoff = growing
    paths, store_dir = _snapshot_store(ds, cutoff, tmp_path)
    with pytest.warns(DeprecationWarning, match="legacy spelling"):
        a = run_aggregation(store_dir, metric="k_stall")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        b = run_aggregation(store_dir, query=Query(metrics=("k_stall",)))
    np.testing.assert_array_equal(a.stats.count, b.stats.count)
    np.testing.assert_array_equal(a.stats.sum, b.stats.sum)
    assert b.from_cache                     # the legacy run warmed it
