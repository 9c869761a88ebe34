"""The live cell at a tiny size on the CPU: a whole run, its control,
and the faults of the ingest path that the comparison has to catch."""

import pytest

import control
import live
import reference

SEED = 2**33 + 99


@pytest.fixture
def live_cell(cell):
    ctx = cell("paper4.live_ingest", kernels=6000, memcpys=750,
               duration_s=24.0)
    ctx["mix"] = dict(ctx["mix"], seed_s=8, warmup_s=1)
    return ctx


def run_tiny(ctx, tmp_path, seconds=3.0, trace=False):
    work = tmp_path / "work"
    work.mkdir(exist_ok=True)
    return live.run_live(ctx, SEED, seconds, trace, str(work),
                         require_tpu=False)


def test_live_run(live_cell, tmp_path):
    res = run_tiny(live_cell, tmp_path)
    assert res["correct"] is True
    mix = live_cell["mix"]
    due = 3.0 * 1000 / mix["batch_ms"] * mix["rate"]   # batches in 3 s
    assert res["attempted"] >= due - 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"fence_p50_ms", "setup_s"}
    assert set(res["compared"]) == set(reference.LIVE_LIMITS)


def test_live_schedule_is_the_capture_rate(live_cell):
    from store import make_dataset
    ds = make_dataset(live_cell["config"], SEED)
    t0 = min(int(t.kernels.start.min()) for t in ds.traces)
    t1 = max(int(t.kernels.end.max()) for t in ds.traces)
    cuts = live.workload.live_cuts(live_cell["mix"], t0, t1)
    assert cuts[0] == t0 + 8 * 10**9 and cuts[-1] >= t1
    assert all(b - a == 100_000_000 for a, b in zip(cuts, cuts[1:]))
    orders = live.split_capture(ds.traces, cuts)
    for tr, o in zip(ds.traces, orders):
        # every event is written exactly once, seed first
        assert sorted(o["k_order"].tolist()) == list(range(len(tr.kernels)))
        assert sorted(o["m_order"].tolist()) == list(range(len(tr.memcpys)))
        assert len(o["k_ends"]) == len(cuts)


def test_live_control_is_not_correct(live_cell):
    out = control.control_live(live_cell, SEED, n_batches=60)
    assert out["correct"] is False
    assert out["numbers"]["minmax_mismatch"] > 0


def test_fault_append_leaves_the_store_unchanged(live_cell, tmp_path,
                                                 monkeypatch):
    from repro.core.generation import AppendReport
    from repro.serve import query_service

    def nothing(db_paths, out_dir, **kw):
        return AppendReport(n_shards=0, n_new_shards=0, dirty_shards=[],
                            appended_rows=0, t_start=0, t_end=0,
                            seconds=0.0)
    monkeypatch.setattr(query_service, "run_append", nothing)
    monkeypatch.setattr(live, "COVER_WAIT_S", 2.0)
    res = run_tiny(live_cell, tmp_path)
    assert res["correct"] is False


def test_fault_half_the_new_rows_left_out(live_cell, tmp_path, monkeypatch):
    from repro.core import generation
    orig = generation.window_left_join

    def half(*a, **kw):
        cols = orig(*a, **kw)
        n = len(cols["k_start"])
        return {c: v[: (n + 1) // 2] for c, v in cols.items()}
    monkeypatch.setattr(generation, "window_left_join", half)
    res = run_tiny(live_cell, tmp_path)
    assert res["correct"] is False
    assert res["compared"]["count_mismatch"]["value"] > 0


def test_fault_fence_altered(live_cell, tmp_path, monkeypatch):
    from repro.serve import stream
    orig = stream.StreamIngestor._diff_fences

    def altered(self, tick):
        out = orig(self, tick)
        for t in out:
            t["hi_fence"] *= 1.0 + 2.0 ** -20
        return out
    monkeypatch.setattr(stream.StreamIngestor, "_diff_fences", altered)
    res = run_tiny(live_cell, tmp_path)
    assert res["correct"] is False
    assert res["compared"]["hi_fence_mismatch"]["value"] > 0


def test_batches_written_past_the_window_are_covered(live_cell, tmp_path,
                                                     monkeypatch):
    """The writer may start batches due after the window's end before
    the stop reaches it; the run waits for the tick that covers them,
    so the final answer and the replay see the same store."""
    import time

    orig = live._writer

    class LateStop:
        def __init__(self, conn):
            self.conn = conn

        def recv(self):
            msg = self.conn.recv()
            if isinstance(msg, dict) and "until" in msg:
                time.sleep(0.7)
            return msg

        def send(self, x):
            self.conn.send(x)

    def writer(conn, *a):
        orig(LateStop(conn), *a)
    monkeypatch.setattr(live, "_writer", writer)
    from repro.serve import query_service
    append = query_service.run_append

    def slow_append(*a, **kw):          # ticks about as slow as on the chip
        time.sleep(1.2)
        return append(*a, **kw)
    monkeypatch.setattr(query_service, "run_append", slow_append)
    res = run_tiny(live_cell, tmp_path)
    assert res["correct"] is True
    mix = live_cell["mix"]
    assert res["attempted"] <= 3.0 * 1000 / mix["batch_ms"] * mix["rate"] + 1
