"""The live cell: a capture still being written, tailed and fenced.

Set-up writes the capture's first ``seed_s`` seconds to the rank DBs,
builds the store from them, serves it on the jax backend, attaches the
DBs through ``POST /v1/ingest/attach`` (the ingest plane's default fence
query: ``k_stall`` p99) and answers that fence query once. A writer
process then appends the rest of the capture to every rank DB, one
batch of ``batch_ms`` of capture every ``batch_ms / rate`` (``rate`` 1 is
the capture's own pace), and a subscriber
process long-polls ``GET /v1/stream/fences``; both were forked before
JAX started. The first ``warmup_s`` seconds of batches are set-up.

A batch's fence latency runs from when it was due to be written to when
the subscriber got the first fence event whose watermarks cover it.
The reference replays every committed tick from the capture itself: the
rows each rank DB's new kernels join to (``reference.append_rows``) and
the p99 fence over the store they make (``reference.LiveStore``).
"""

from __future__ import annotations

import http.client
import json
import multiprocessing as mp
import os
import sqlite3
import threading
import time
import types
from typing import Dict, List

import numpy as np

import instrument
import reference
import workload
from run import Compiles, chip_devices, finish, log, memory_peak

_FORK = mp.get_context("fork")
COVER_WAIT_S = 60.0


# --- the capture, cut into what set-up writes and what the writer appends ----

def _between(t, lo, hi):
    return (t > lo) & (t <= hi)


def split_capture(traces, cuts: List[int]) -> List[Dict]:
    """Per rank: the order in which kernels and memcpys reach the DB
    (their rowids, from 1) and where each batch ends in that order.
    Rows of the seed are those that end by ``cuts[0]``; batch i those
    that end in ``(cuts[i-1], cuts[i]]``."""
    out = []
    for tr in traces:
        ke, me = tr.kernels.end, tr.memcpys.end
        k_parts = [np.flatnonzero(ke <= cuts[0])]
        m_parts = [np.flatnonzero(me <= cuts[0])]
        for lo, hi in zip(cuts[:-1], cuts[1:]):
            k_parts.append(np.flatnonzero(_between(ke, lo, hi)))
            m_parts.append(np.flatnonzero(_between(me, lo, hi)))
        out.append({
            "k_order": np.concatenate(k_parts),
            "m_order": np.concatenate(m_parts),
            "k_ends": np.cumsum([len(p) for p in k_parts]),
            "m_ends": np.cumsum([len(p) for p in m_parts]),
        })
    return out


def _rank_part(tr, order: Dict, batch: int):
    """The kernels and memcpys of ``batch`` (0 = the seed) of one rank."""
    from repro.core.events import RankTrace
    k0 = 0 if batch == 0 else order["k_ends"][batch - 1]
    m0 = 0 if batch == 0 else order["m_ends"][batch - 1]
    k = order["k_order"][k0:order["k_ends"][batch]]
    m = order["m_order"][m0:order["m_ends"][batch]]
    return RankTrace(rank=tr.rank, kernels=tr.kernels.take(k),
                     memcpys=tr.memcpys.take(m), gpus=tr.gpus,
                     names=tr.names)


def kernel_columns(tr, idx) -> Dict:
    k = tr.kernels
    return {"start": k.start[idx], "end": k.end[idx],
            "device": k.device[idx], "memory_stall": k.memory_stall[idx],
            "name_id": k.name_id[idx]}


def memcpy_columns(tr, idx) -> Dict:
    m = tr.memcpys
    return {"start": m.start[idx], "end": m.end[idx],
            "device": m.device[idx], "bytes": m.bytes[idx],
            "copy_kind": m.copy_kind[idx]}


def _rowid_hi(path: str):
    conn = sqlite3.connect(path)
    try:
        return [int(conn.execute(f"SELECT MAX(rowid) FROM {t}").fetchone()[0]
                    or 0) for t in ("CUPTI_ACTIVITY_KIND_KERNEL",
                                    "CUPTI_ACTIVITY_KIND_MEMCPY")]
    finally:
        conn.close()


# --- the writer and the subscriber (forked before JAX starts) ------------------

def _writer(conn, traces, orders, paths, step_s: float) -> None:
    from repro.core.events import append_rank_db
    t0 = conn.recv()["t0"]
    # every batch due before ``until`` is written, late or not, so that a
    # writer that falls behind shows in the latency instead of hiding it
    until, stop = [float("inf")], threading.Event()

    def listen() -> None:
        until[0] = conn.recv()["until"]
        stop.set()
    threading.Thread(target=listen, daemon=True).start()
    done = []
    n = len(orders[0]["k_ends"]) - 1
    for i in range(1, n + 1):
        due = t0 + (i - 1) * step_s
        while not stop.is_set() and time.monotonic() < due:
            stop.wait(min(due - time.monotonic(), 0.05))
        if due >= until[0]:
            break
        for tr, order, p in zip(traces, orders, paths):
            append_rank_db(p, _rank_part(tr, order, i))
        done.append({"batch": i, "due": due, "written": time.monotonic(),
                     "marks": {p: _rowid_hi(p) for p in paths}})
    conn.send(done)


def _subscriber(conn) -> None:
    port = conn.recv()["port"]
    events, since = [], 0
    # stops once it has every event up to the seq the stop message names,
    # or COVER_WAIT_S after that message
    last_seq, give_up, stop = [0], [0.0], threading.Event()

    def listen() -> None:
        last_seq[0] = conn.recv()["seq"]
        give_up[0] = time.monotonic() + COVER_WAIT_S
        stop.set()
    threading.Thread(target=listen, daemon=True).start()
    http_conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    while not stop.is_set() or (since < last_seq[0]
                                and time.monotonic() < give_up[0]):
        try:
            http_conn.request(
                "GET", f"/v1/stream/fences?since={since}&timeout_s=0.5")
            resp = http_conn.getresponse()
            body = json.loads(resp.read())
        except (OSError, http.client.HTTPException, ValueError):
            http_conn.close()
            http_conn = http.client.HTTPConnection("127.0.0.1", port,
                                                   timeout=30)
            time.sleep(0.01)
            continue
        now = time.monotonic()
        for e in body.get("events", []):
            events.append({"arrival": now, "event": e})
        since = body.get("next_since", since)
    conn.send(events)


def _post(port: int, path: str, body) -> Dict:
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        c.request("POST", path, body=json.dumps(body).encode(),
                  headers={"Content-Type": "application/json"})
        resp = c.getresponse()
        data = json.loads(resp.read())
        if resp.status != 200:
            raise RuntimeError(f"{path}: HTTP {resp.status}: {data}")
        return data
    finally:
        c.close()


def _covers(ingest: Dict, marks: Dict) -> bool:
    """Whether an ingest tick's watermarks reach a batch's rowid marks,
    in both tables of every rank DB."""
    wm = ingest.get("watermarks") or {}
    return all(a >= b for p, m in marks.items()
               for a, b in zip(wm.get(p, (0, 0)), m))


# --- one run ----------------------------------------------------------------------

def run_live(ctx: Dict, seed: int, seconds: float, trace: bool, work: str,
             require_tpu: bool = True) -> Dict:
    from store import build_store, make_dataset, write_rank_dbs

    from run import T_PROCESS
    config, mix = ctx["config"], ctx["mix"]
    ds = make_dataset(config, seed)
    t_first = min(int(tr.kernels.start.min()) for tr in ds.traces)
    t_last = max(int(tr.kernels.end.max()) for tr in ds.traces)
    cuts = workload.live_cuts(mix, t_first, t_last)
    orders = split_capture(ds.traces, cuts)
    seed_traces = [_rank_part(tr, o, 0) for tr, o in zip(ds.traces, orders)]
    paths = [os.path.abspath(p) for p in write_rank_dbs(
        seed_traces, os.path.join(work, "dbs"))]
    store_dir = os.path.join(work, "store")
    rep = build_store(paths, store_dir, config)
    log(f"seed store: {rep.joined_rows} joined rows in {rep.n_shards} "
        f"shards; {len(cuts) - 1} batches to write; ready at "
        f"{time.monotonic() - T_PROCESS:.3f} s")

    # one batch of batch_ms of capture every batch_ms / rate of wall time
    step_s = int(mix["batch_ms"]) / 1000.0 / float(mix.get("rate", 1.0))
    w_parent, w_child = _FORK.Pipe()
    s_parent, s_child = _FORK.Pipe()
    writer = _FORK.Process(target=_writer, daemon=True, args=(
        w_child, ds.traces, orders, paths, step_s))
    writer.start()
    subscriber = _FORK.Process(target=_subscriber, daemon=True,
                               args=(s_child,))
    subscriber.start()

    svc, recorder = None, None
    trace_dir = os.path.join(work, "trace")
    try:
        devices = chip_devices(int(ctx["cell"]["chips"]), require_tpu)
        from repro.compile_cache import enable_compile_cache
        from repro.core import PipelineConfig, VariabilityPipeline
        log(f"compile cache: {enable_compile_cache()}")
        compiles = Compiles().install()
        pipe = VariabilityPipeline(PipelineConfig(
            n_ranks=int(config["n_ranks"]), backend="jax",
            devices=list(devices)))
        svc = pipe.serve(store_dir, port=0)
        port = svc.cfg.port
        _post(port, "/v1/ingest/attach", {"db_paths": paths})
        _post(port, "/v1/query", mix["fence_query"])
        s_parent.send({"port": port})
        t0 = time.monotonic() + 0.2
        w_parent.send({"t0": t0})
        w0 = t0 + float(mix["warmup_s"])
        time.sleep(max(0.0, w0 - time.monotonic()))
        setup_s = time.monotonic() - T_PROCESS
        log(f"set-up: {setup_s:.3f} s, "
            f"{compiles.summary(0.0, time.monotonic())}")
        w1 = w0 + float(seconds)
        if trace:
            from jax.profiler import TraceAnnotation
            recorder = instrument.start(trace_dir)
            with TraceAnnotation("bench.window"):
                time.sleep(max(0.0, w1 - time.monotonic()))
            instrument.stop(recorder)
        else:
            time.sleep(max(0.0, w1 - time.monotonic()))
        w_parent.send({"until": w1})
        batches = w_parent.recv()
        # wait for the tick that covers every batch written, those the
        # writer began just past the window's end included: the final
        # answer and the replay must see the same store
        deadline = time.monotonic() + COVER_WAIT_S
        while time.monotonic() < deadline and batches:
            st = svc.ingestor.stats() if svc.ingestor else {}
            if _covers(st.get("last_ingest") or {}, batches[-1]["marks"]):
                break
            time.sleep(0.05)
        s_parent.send({"seq": svc.ingestor.hub.seq})
        events = s_parent.recv()
        peak = memory_peak(devices)
        # the last tick's fence answer, as a client reads it
        final = _post(port, "/v1/query", mix["fence_query"])["results"][0]
    finally:
        if svc is not None:
            svc.stop()
        for proc in (writer, subscriber):
            proc.join(timeout=10 if svc is not None else 0)
            if proc.is_alive():
                proc.kill()
                proc.join()

    in_window = [b for b in batches if w0 <= b["due"] < w1]
    lat, unmatched = [], 0
    for b in in_window:
        hit = next((e for e in events
                    if _covers(e["event"].get("ingest") or {}, b["marks"])),
                   None)
        if hit is None:
            unmatched += 1
        else:
            lat.append((hit["arrival"] - b["due"]) * 1e3)
    log(f"window: {len(in_window)} batches due, {len(lat)} fenced, "
        f"{len(events)} events, {compiles.summary(w0, w1)}")
    # how late the writer ran: its own write, and any time it fell behind
    lag = sorted((b["written"] - b["due"]) * 1e3 for b in in_window) or [0]
    log(f"writer lag ms: median {lag[len(lag) // 2]:.1f}, "
        f"max {lag[-1]:.1f}")

    t_ref = time.monotonic()
    numbers = reference.empty_numbers(reference.LIVE_LIMITS)
    notes: List[str] = []
    numbers["unanswered"] += unmatched
    store = replay(ds.traces, orders, seed_traces, paths, events, config,
                   numbers, notes)
    reference.compare(final, store.answer(), numbers, notes,
                      tag="final fence query")
    log(f"reference: {time.monotonic() - t_ref:.3f} s")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    mctx = types.SimpleNamespace(
        fence_ms=lat, window={"t_begin": w0, "t_end": w1},
        compiles=compiles,
        spans=recorder.spans if recorder else [],
        reduces=recorder.reduces if recorder else [],
        n_devices=len(devices), device_kind=devices[0].device_kind,
        trace=None, setup_s=setup_s, seconds=float(seconds), done=[])
    return finish(ctx, mctx, device, numbers, reference.LIVE_LIMITS, notes,
                  trace, trace_dir, attempted=len(in_window),
                  failed=unmatched)


def replay(traces, orders, seed_traces, paths, events, config, numbers,
           notes) -> "reference.LiveStore":
    """Replay every tick the subscriber saw against the reference;
    returns the reference's store after the last one."""
    gen = config["generation"]
    window_ns, cap = int(gen["join_window_ns"]), int(gen["join_cap"])
    table, plan = reference.build_table(
        seed_traces, int(gen["interval_ns"]), window_ns, cap,
        int(config["n_ranks"]))
    live = reference.LiveStore(table, plan)
    prev = {p: (int(o["k_ends"][0]), int(o["m_ends"][0]))
            for p, o in zip(paths, orders)}
    state = None
    seqs = [e["event"]["seq"] for e in events]
    if seqs != sorted(seqs) or len(set(seqs)) != len(seqs):
        numbers["count_mismatch"] += 1
        notes.append("fence events out of order or repeated")
    for item in events:
        e = item["event"]
        ing = e.get("ingest") or {}
        wm = {p: tuple(int(x) for x in ing.get("watermarks", {}).get(
            p, prev[p])) for p in paths}
        rows, max_end = [], 0
        for r, (tr, o, p) in enumerate(zip(traces, orders, paths)):
            (k_lo, m_lo), (k_hi, m_hi) = prev[p], wm[p]
            got, end = reference.append_rows(
                kernel_columns(tr, o["k_order"][k_lo:k_hi]),
                memcpy_columns(tr, o["m_order"][m_lo:m_hi]),
                memcpy_columns(tr, o["m_order"][:m_lo]), r, window_ns, cap)
            if got is not None:
                rows.append(got)
                max_end = max(max_end, end)
        n_rows = sum(len(x["k_start"]) for x in rows)
        if int(ing.get("rows_ingested", -1)) != n_rows:
            numbers["count_mismatch"] += 1
            if len(notes) < 20:
                notes.append(f"tick {e.get('tick_seq')}: rows_ingested "
                             f"{ing.get('rows_ingested')} vs {n_rows}")
        if rows:
            live.append({c: np.concatenate([x[c] for x in rows])
                         for c in ("k_start", "k_stall")}, max_end)
        prev = wm
        for t in e.get("transitions", []):
            state = (tuple(t["anomalous"]), float(t["hi_fence"]))
            if not any(abs(hi - state[1]) == 0.0
                       for _, hi in live.fences()):
                numbers["hi_fence_mismatch"] += 1
                if len(notes) < 20:
                    notes.append(f"tick {e.get('tick_seq')}: hi_fence "
                                 f"{state[1]!r} vs "
                                 f"{[h for _, h in live.fences()]}")
        if state is not None and state[0] not in {
                s for s, _ in live.fences()}:
            numbers["fence_mismatch"] += 1
            if len(notes) < 20:
                notes.append(f"tick {e.get('tick_seq')}: anomalous "
                             f"{state[0]} vs "
                             f"{[s for s, _ in live.fences()]}")
    return live
