"""The program's spans in a traced run (``program_spans``): the trace
reduction's existing keys do not see them, idle gaps are named for the
innermost of them, and every reader finds nothing to read in a trace
without them."""

import glob
import os
import time
import types

import pytest

import program_spans as ps
import run
import trace_reduce as tr

NEW_METRICS = (
    "shard_read_ms.cold", "scan_prep_ms.cold", "reduce_host_ms.cold",
    "h2d_mb.cold", "d2h_wait_ms.cold", "merge_ms.cold",
    "cache_write_ms.cold", "moments_roofline.cold",
    "histogram_roofline.cold", "admit_wait_ms.hot", "summary_read_ms.hot",
    "commit_ms.hot", "respond_ms.hot", "append_read_ms.ingest",
    "append_join_ms.ingest", "append_write_ms.ingest",
    "fence_exec_ms.ingest")


def _record(log_dir, with_program_spans):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.core.spans import span

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
        with TraceAnnotation("bench.scan_prep"):
            if with_program_spans:
                with span("repro.scan.prep", rows=40):
                    time.sleep(0.08)
            else:
                time.sleep(0.08)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    return glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    return d, tr.load(_record(d, True))


def _without_program_spans(pd):
    """The same trace with every ``repro.*`` event taken out."""
    ns = types.SimpleNamespace
    return ns(planes=[ns(name=p.name, lines=[
        ns(name=line.name, events=[ev for ev in line.events
                                   if not ev.name.startswith("repro.")])
        for line in p.lines]) for p in pd.planes])


def test_reduce_keys_unchanged_by_program_spans(traced):
    _, pd = traced
    with_spans = tr.reduce(pd)
    assert with_spans == tr.reduce(_without_program_spans(pd))
    assert not any(k.startswith("repro.") for k in with_spans["self_ns"])
    assert with_spans["idle_gaps"][0][0] == "bench.scan_prep"


def test_gap_named_for_the_inner_program_span(traced):
    _, pd = traced
    red = ps.reduce(pd)
    who, secs = red["idle_gaps"][0]
    assert who == "repro.scan.prep" and secs >= 0.07
    rec = red["names"]["repro.scan.prep"]
    assert rec["count"] == 1 and rec["stats"] == {"rows": 40}
    assert 70e6 <= rec["self_ns"] <= rec["total_ns"] < 200e6
    assert ps.reduce(_without_program_spans(pd)) is None


def test_innermost_and_the_naming_order():
    spans = [(0, 100, "repro.tick.exec", "t1"),
             (10, 90, "repro.reduce.dispatch", "t1"),
             (20, 70, ps.COMPILE, "t1"),
             (0, 100, "repro.respond", "t2")]
    own = ps.innermost([s for s in spans if s[2] not in ps.OUTER])
    assert sorted(own) == [(0, 100, "repro.respond", "t2"),
                           (10, 20, "repro.reduce.dispatch", "t1"),
                           (20, 70, ps.COMPILE, "t1"),
                           (70, 90, "repro.reduce.dispatch", "t1")]
    bench = [(0, 100, "bench.tick_exec", "t1"),
             (0, 40, "bench.device_reduce", "t1")]
    got = ps.name_gaps([(15, 75), (92, 99), (200, 300)],
                       spans[:3], bench)
    # compile covers 50 of the first gap, the dispatch's own time 10; the
    # second lies in the outer tick alone: the bench rule, which names
    # the outer span where nothing else covers it
    assert [w for w, _ in got] == [ps.COMPILE, "bench.tick_exec",
                                   "no_bench_span"]


def test_new_readers_read_nothing_without_program_spans(tmp_path):
    d = str(tmp_path / "trace")
    pd = tr.load(_record(d, False))
    ctx = types.SimpleNamespace(
        trace=tr.reduce(pd), trace_dir=d, done=[
            {"cache_hit": False, "inflight_hit": False}], reduces=[],
        device_kind="TPU v5 lite", window={"t_begin": 0.0, "t_end": 1e9})
    for name in NEW_METRICS:
        assert run.metric_reader(name)(ctx) is None, name


@pytest.mark.parametrize("name", ["paper4.explore_cold",
                                  "paper4.explore_hot",
                                  "paper4.live_ingest"])
def test_traced_tiny_run_reads_every_new_metric(name, cell, tmp_path,
                                                 monkeypatch):
    import live

    monkeypatch.setitem(tr.PEAKS, "cpu", {"hbm_bytes_per_s": 1e12,
                                          "source": "test only"})
    work = tmp_path / "work"
    work.mkdir()
    if name == "paper4.live_ingest":
        ctx = cell(name, kernels=6000, memcpys=750, duration_s=24.0)
        ctx["mix"] = dict(ctx["mix"], seed_s=8, warmup_s=1)
        res = live.run_live(ctx, 2**33 + 5, 3.0, True, str(work),
                            require_tpu=False)
    else:
        res = run.run_explore(cell(name), 2**33 + 5, 2.0, True, str(work),
                              require_tpu=False)
    assert res["correct"] is True
    suffix = {"paper4.explore_cold": ".cold", "paper4.explore_hot": ".hot",
              "paper4.live_ingest": ".ingest"}[name]
    want = {m for m in NEW_METRICS if m.endswith(suffix)}
    got = res["metrics"]
    assert want <= set(got)
    for m in want:
        assert got[m]["value"] >= 0, m
    for m in ("moments_roofline.cold", "histogram_roofline.cold"):
        if m in want:
            assert 0 < got[m]["value"] <= 100
