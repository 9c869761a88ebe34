"""Query-serving front door tests: HTTP round-trip correctness against
direct ``run_queries``, deterministic admission batching (N submitted
requests drain into ONE fused plan), the per-request result-size budget
(HTTP 413), the byte-budgeted summary LRU — which must never evict a
key touched within the current tick — and the concurrency layer:
parallel-scan bit-identity, overlapping-tick in-flight dedup, the
dead-worker 503/tick_timeout contract, and the pack-byte-budget LRU.
The summary LRU's size ledger is checked against the rule it replaced,
a listing of the whole store on every commit."""

import json
import os
import shutil
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import (Query, QueryPlan, SyntheticSpec, TraceStore,
                        append_rank_db, generate_synthetic,
                        run_generation, run_queries, trace_remainder,
                        truncate_trace, write_rank_db)
from repro.core.aggregation import ScanPool
from repro.core.tracestore import summary_filename
from repro.serve.query_service import (BudgetExceeded, QueryService,
                                       ServiceConfig, _ByteBudgetLRU)


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    spec = SyntheticSpec(n_ranks=2, kernels_per_rank=2000,
                         memcpys_per_rank=300, duration_s=20.0, seed=7)
    ds = generate_synthetic(spec)
    root = tmp_path_factory.mktemp("svc_base")
    paths = []
    for tr in ds.traces:
        p = str(root / f"rank{tr.rank}.sqlite")
        write_rank_db(p, tr)
        paths.append(p)
    store_dir = str(root / "store")
    run_generation(paths, store_dir, n_ranks=2)
    return store_dir


@pytest.fixture
def store_dir(base, tmp_path):
    dst = str(tmp_path / "s")
    shutil.copytree(base, dst)
    return dst


def _post(port, specs, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query",
        data=json.dumps(specs).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_round_trip_matches_direct_run_queries(store_dir):
    """A served answer is the engine's answer: group counts/means from
    the HTTP JSON equal a direct ``run_queries`` on the same store, and
    the response carries the engine's provenance fields."""
    svc = QueryService(store_dir, ServiceConfig(tick_ms=5.0, port=0))
    svc.start(serve_http=True)
    try:
        status, body = _post(svc.cfg.port,
                             [{"metrics": ["k_stall"],
                               "group_by": "m_kind"}])
        assert status == 200
        r = body["results"][0]
        direct = run_queries(
            TraceStore(store_dir),
            [Query(metrics=("k_stall",), group_by="m_kind")])[0]
        g = direct.result.grouped
        cnt = g.count.sum(axis=0)
        tot = g.sum.sum(axis=0)
        for gi, gk in enumerate(
                np.asarray(direct.result.group_keys).ravel()):
            got = r["groups"][f"{float(gk):g}"]["k_stall"]
            assert got["count"] == int(cnt[gi, 0])
            np.testing.assert_allclose(got["mean"],
                                       tot[gi, 0] / cnt[gi, 0])
        assert r["n_samples"] == int(cnt.sum())
        for f in ("cache_hit", "recomputed_shards", "partial_hits",
                  "shards_pruned", "rows_scanned", "provenance"):
            assert f in r
        # second ask: pure summary hit through the shared store
        status, body = _post(svc.cfg.port,
                             [{"metrics": ["k_stall"],
                               "group_by": "m_kind"}])
        assert status == 200
        assert body["results"][0]["cache_hit"]
    finally:
        svc.stop()


def test_submitted_requests_drain_into_one_fused_plan(store_dir):
    """Deterministic batching (no worker thread): three requests with
    five queries total, submitted before one ``drain_once``, ride ONE
    fused plan — every response reports the full fused width and
    ``batched_fused``."""
    svc = QueryService(store_dir, ServiceConfig(tick_ms=1.0))
    pendings = [
        svc.submit([Query(metrics=("k_stall",), group_by="m_kind")]),
        svc.submit([Query(metrics=("m_duration",), ranks=(0,)),
                    Query(metrics=("m_bytes",), group_by="k_device")]),
        svc.submit([Query(metrics=("k_stall",), group_by="m_kind"),
                    Query(metrics=("k_stall",), anomaly_score="p99")]),
    ]
    served = svc.drain_once(block_s=0.0)
    assert served == 3
    for p in pendings:
        assert p.done.is_set() and p.error is None
        assert p.tick_info["fused_width"] == 5
        assert p.tick_info["batched_fused"] is True
        assert len(p.results) == len(p.queries)
    assert pendings[2].results[1]["anomalous_bins"] >= 0
    assert svc.stats()["max_fused_width"] == 5
    assert svc.drain_once(block_s=0.0) == 0        # queue drained


def test_over_budget_request_is_rejected_413(store_dir):
    """A pathological re-binning (1 us bins over the whole trace) blows
    the estimated result-cell budget at ADMISSION — BudgetExceeded from
    submit, HTTP 413 over the wire — without ever touching a shard."""
    svc = QueryService(store_dir, ServiceConfig(
        tick_ms=1.0, max_cells_per_request=100_000, port=0))
    with pytest.raises(BudgetExceeded):
        svc.submit([Query(metrics=("k_stall",), interval_ns=1_000)])
    svc.start(serve_http=True)
    try:
        status, body = _post(svc.cfg.port,
                             [{"metrics": ["k_stall"],
                               "interval_ns": 1000}])
        assert status == 413
        assert body["error"]["code"] == "budget_exceeded"
        assert "budget" in body["error"]["message"]
        assert QueryService(store_dir).store.io_counts["shard_reads"] == 0
    finally:
        svc.stop()


def test_lru_never_evicts_summary_read_within_same_tick(store_dir):
    """Byte budget of 1: eviction pressure is permanent, yet each tick's
    own summary keys survive that tick (a result can never be evicted
    between compute and read-back); the PREVIOUS tick's keys are the
    ones reclaimed."""
    svc = QueryService(store_dir, ServiceConfig(
        tick_ms=1.0, summary_budget_bytes=1))
    q_a = Query(metrics=("k_stall",), group_by="m_kind")
    q_b = Query(metrics=("m_duration",), group_by="m_kind")

    p = svc.submit([q_a])
    svc.drain_once(block_s=0.0)
    assert p.error is None and p.tick_info["evicted"] == 0
    keys_after_a = set(svc.store.summary_keys())
    assert len(keys_after_a) == 1                  # A survives its tick

    p = svc.submit([q_b])
    svc.drain_once(block_s=0.0)
    assert p.error is None and p.tick_info["evicted"] == 1
    keys_after_b = set(svc.store.summary_keys())
    assert len(keys_after_b) == 1                  # B survives, A gone
    assert keys_after_b != keys_after_a
    (key_b,) = keys_after_b
    assert os.path.exists(os.path.join(svc.store.root,
                                       summary_filename(key_b)))
    # evicting a summary is safe: A recomputes from partials, no rescan
    fresh = TraceStore(store_dir)
    res = run_queries(fresh, [q_a])[0]
    assert res.result.partial_hits > 0
    assert fresh.io_counts["shard_reads"] == 0


# --- concurrency: scan pool, pipelined ticks, pack LRU ----------------------

def test_scan_pool_results_bit_identical_to_serial(store_dir):
    """Cold fused scans through a 4-worker :class:`ScanPool` produce
    EXACTLY the serial path's tensors (array equality, not allclose):
    each shard partial is a pure function of its shard and the merge
    consumes them in fixed shard order, never completion order."""
    queries = [Query(metrics=("k_stall",), group_by="m_kind"),
               Query(metrics=("m_duration", "m_bytes"),
                     group_by="m_kind"),
               Query(metrics=("k_stall",), anomaly_score="p99")]
    store = TraceStore(store_dir)
    store.clear_summaries()
    store.clear_partials()
    serial = run_queries(store, queries)
    store.clear_summaries()
    store.clear_partials()
    with ScanPool(4) as pool:
        pooled = run_queries(store, queries, pool=pool)
        util = pool.utilization()
    assert util["workers"] == 4 and util["tasks"] > 0
    for a, b in zip(serial, pooled):
        assert np.array_equal(a.result.group_keys, b.result.group_keys)
        for name, sa in a.result.reduced.items():
            sb = b.result.reduced[name]
            for f in sa.fields:
                assert np.array_equal(getattr(sa, f), getattr(sb, f))


def test_overlapping_ticks_share_inflight_computation(store_dir,
                                                      monkeypatch):
    """Pipelined: a query admitted while an earlier tick is still
    computing the same canonical query BORROWS that tick's slot — one
    execution serves both, and the borrower's response says so
    (``inflight_hit`` provenance, ``inflight_hits`` stat)."""
    started, release = threading.Event(), threading.Event()
    orig = QueryService._exec_tick

    def stalling_exec(self, tick):
        if tick.owned:                 # owner tick: stall mid-flight
            started.set()
            release.wait(10)
        orig(self, tick)

    monkeypatch.setattr(QueryService, "_exec_tick", stalling_exec)
    svc = QueryService(store_dir, ServiceConfig(
        tick_ms=1.0, pipeline_depth=2, scan_workers=1))
    svc.start(serve_http=False)
    try:
        q = Query(metrics=("k_stall",), group_by="m_kind")
        pa = svc.submit([q])
        assert started.wait(5)
        pb = svc.submit([q])           # same canonical key, next tick
        time.sleep(0.2)                # let tick 2 admit and borrow
        release.set()
        assert pa.done.wait(10) and pb.done.wait(10)
        assert pa.error is None and pb.error is None
        assert pa.results[0].get("inflight_hit") is None
        assert pb.results[0]["inflight_hit"] is True
        assert pb.results[0]["groups"] == pa.results[0]["groups"]
        assert svc.stats()["inflight_hits"] == 1
    finally:
        release.set()
        svc.stop()


def test_dead_tick_worker_yields_503_tick_timeout(store_dir,
                                                  monkeypatch):
    """A tick worker killed mid-tick (its tick never fills slots, never
    commits) must surface as HTTP 503 with error code ``tick_timeout``
    within ``request_timeout_s`` — never a handler parked forever — and
    the service keeps serving fresh keys afterwards."""
    killed = threading.Event()
    orig = QueryService._pipeline_task

    def dying_task(self, tick):
        if not killed.is_set():
            killed.set()               # first tick: worker dies here —
            return                     # no slot fill, no commit
        orig(self, tick)

    monkeypatch.setattr(QueryService, "_pipeline_task", dying_task)
    svc = QueryService(store_dir, ServiceConfig(
        tick_ms=1.0, pipeline_depth=2, scan_workers=1,
        request_timeout_s=0.5, port=0))
    svc.start(serve_http=True)
    try:
        status, body = _post(svc.cfg.port,
                             [{"metrics": ["k_stall"],
                               "group_by": "m_kind"}], timeout=30)
        assert status == 503
        assert body["error"]["code"] == "tick_timeout"
        # the pipeline survived its dead worker: a different canonical
        # query rides a healthy tick
        status, body = _post(svc.cfg.port,
                             [{"metrics": ["m_bytes"],
                               "group_by": "k_device"}], timeout=30)
        assert status == 200
        assert body["results"][0]["n_samples"] > 0
    finally:
        svc.stop()


def test_pack_budget_evicts_only_committed_ticks_packs(store_dir):
    """``pack_budget_bytes=1`` is permanent pressure, yet a tick's packs
    are immune while it is in flight: the full-store tick keeps every
    pack through its own commit, and they are reclaimed by a later
    tick that only touches a shard subset. Evicted packs are derived
    data — the next cold ask recomputes and answers identically."""
    svc = QueryService(store_dir, ServiceConfig(
        tick_ms=1.0, pack_budget_bytes=1))
    q_full = Query(metrics=("k_stall",), group_by="m_kind")
    p = svc.submit([q_full])
    svc.drain_once(block_s=0.0)
    assert p.error is None
    first = p.results[0]
    # own-tick immunity: every pack this tick wrote survived its commit
    packs_after_full = set(svc.store.pack_sizes())
    assert packs_after_full
    assert svc.stats()["pack_evictions"] == 0

    # a time-windowed tick touches only early shards; everything else
    # is now fair game for the byte budget
    man = svc.man
    span = int(man.t_end - man.t_start)
    q_win = Query(metrics=("k_stall",),
                  time_window=(int(man.t_start),
                               int(man.t_start + span // 4)))
    p = svc.submit([q_win])
    svc.drain_once(block_s=0.0)
    assert p.error is None
    assert svc.stats()["pack_evictions"] > 0
    assert set(svc.store.pack_sizes()) < packs_after_full

    # packs are pure derived data: cold re-ask, identical answer
    svc.store.clear_summaries()
    p = svc.submit([q_full])
    svc.drain_once(block_s=0.0)
    assert p.error is None
    assert p.results[0]["groups"] == first["groups"]
    assert p.results[0]["n_samples"] == first["n_samples"]


# --- the summary LRU's size ledger against the full-listing rule ------------

class _FullListingLRU(_ByteBudgetLRU):
    """The reference rule: list and stat every summary file on every
    commit, then evict least-recently-used, non-immune keys until the
    listed total fits the budget."""

    def __init__(self, store, budget_bytes):
        super().__init__(budget_bytes)
        self.store = store

    def evict(self, written=(), rescan=False):
        sizes = {k: os.path.getsize(os.path.join(self.store.root,
                                                 summary_filename(k)))
                 for k in self.store.summary_keys()}
        self._sync_order(sizes)
        total = sum(sizes.values())
        immune = self.immune()
        evicted = 0
        for k in list(self._order):
            if total <= self.budget:
                break
            if k in immune:
                continue
            os.remove(os.path.join(self.store.root, summary_filename(k)))
            total -= sizes.pop(k)
            self._order.pop(k)
            evicted += 1
        self.evictions += evicted
        return evicted, True, 1 + len(sizes)


def _disk_sizes(store):
    return {k: os.path.getsize(os.path.join(store.root,
                                            summary_filename(k)))
            for k in store.summary_keys()}


@pytest.fixture(scope="module")
def live_ds():
    ds = generate_synthetic(SyntheticSpec(
        n_ranks=2, kernels_per_rank=1500, memcpys_per_rank=200,
        duration_s=8.0, seed=13))
    return ds, int(ds.traces[0].kernels.start.min()) + 4 * 10**9


@pytest.mark.parametrize("budget", [1, 12_000, 10**9])
def test_ledger_lru_evicts_what_the_full_listing_rule_evicts(
        live_ds, tmp_path, budget):
    """Two services on copies of one store run the same ticks: one with
    the size ledger, one with the full listing on every commit. They
    evict the same keys at every commit and keep the same LRU order,
    through a budget crossed again and again (summaries of 4-17 KB), a
    summary planted before start (adopted at the cold end), same-tick
    immunity, an out-of-band ``clear_summaries`` (the ledger overstates,
    then corrects) and an ingest tick (which lists the store). After
    every commit the ledger never understates the store, and the store
    fits the budget unless only the committing tick's keys remain."""
    ds, cutoff = live_ds
    paths = []
    for tr in ds.traces:
        p = str(tmp_path / f"rank{tr.rank}.sqlite")
        write_rank_db(p, truncate_trace(tr, cutoff))
        paths.append(p)
    built = str(tmp_path / "built")
    run_generation(paths, built, n_ranks=2)
    planted = "0" * 16
    with open(os.path.join(built, summary_filename(planted)), "wb") as f:
        f.write(b"\0" * 4000)
    cfg = dict(tick_ms=1.0, summary_budget_bytes=budget)
    led = QueryService(shutil.copytree(built, str(tmp_path / "led")),
                       ServiceConfig(**cfg))
    ref = QueryService(shutil.copytree(built, str(tmp_path / "ref")),
                       ServiceConfig(**cfg))
    ref.cache = _FullListingLRU(ref.store, budget)
    assert led.cache._sizes == {planted: 4000}

    qa = Query(metrics=("k_stall",), group_by="m_kind")
    qb = Query(metrics=("m_duration",), group_by="m_kind")
    qc = Query(metrics=("m_bytes",))
    qd = Query(metrics=("k_stall", "m_duration"), group_by="k_device")
    qe = Query(metrics=("k_stall",), anomaly_score="p99")
    steps = [[qa], [qb], [qa], [qc, qd], [qa, qb], "clear", [qe], [qa],
             [qb], "append", "ingest", [qc], [qa], [qd], [qa]]
    for i, step in enumerate(steps):
        if step == "clear":
            led.store.clear_summaries()
            ref.store.clear_summaries()
            assert sum(led.cache._sizes.values()) > 0   # overstates
            continue
        if step == "append":
            for tr, p in zip(ds.traces, paths):
                append_rank_db(p, trace_remainder(tr, cutoff))
            continue
        queries = [qa] if step == "ingest" else step
        rescans = led.cache.rescans
        infos = []
        for svc in (led, ref):
            if step == "ingest":
                pend = [svc.submit_ingest(paths, queries)]
            else:
                pend = [svc.submit([q]) for q in queries]
            assert svc.drain_once(block_s=0.0) == len(pend)
            assert all(p.error is None for p in pend), pend[0].error
            infos.append(pend[0].tick_info)
        if step == "ingest":
            assert infos[0]["ingest"]["rows_ingested"] > 0
            assert led.cache.rescans == rescans + 1
            assert planted not in led.cache._sizes
        on_disk = _disk_sizes(led.store)
        if i == 0:
            # the planted summary sits at the cold end: first to go
            assert (next(iter(led.cache._order)) == planted
                    if planted in on_disk else infos[0]["evicted"] >= 1)
        assert infos[0]["evicted"] == infos[1]["evicted"], i
        assert on_disk == _disk_sizes(ref.store), i
        assert [k for k in led.cache._order if k in on_disk] \
            == list(ref.cache._order), i
        own = {ln.summary_key for ln in QueryPlan.compile(
            led.store, queries).lanes}
        assert own <= set(on_disk), i                  # same-tick immunity
        assert sum(on_disk.values()) <= budget or set(on_disk) <= own, i
        assert sum(led.cache._sizes.values()) >= sum(on_disk.values()), i
    assert led.cache._sizes == _disk_sizes(led.store)
    assert led.cache.evictions == ref.cache.evictions
    if budget == 10**9:
        assert led.cache.evictions == 0
        assert led.cache.rescans == 1                   # the ingest tick
    else:
        assert led.cache.evictions > 0
