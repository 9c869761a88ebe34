"""The exchange between chips: the link bytes of a reduce's all-reduce
(``exchange.collective_bytes``), the readers of ``exchange_mb.cold`` and
``collective_roofline.cold`` on a synthetic span and trace, the cases in
which they find nothing to read, and a traced run of the four-chip cell
on four virtual CPU devices."""

import json
import os
import subprocess
import sys
import types

import pytest

import exchange
import program_spans
import run

HERE = os.path.dirname(os.path.abspath(__file__))
ICI = 1600e9 / 8            # one TPU v5e chip's interconnect, bytes/s


def call(reducer="moments", metrics=2, n_seg=128, devices=4, **more):
    return dict(reducer=reducer, metrics=metrics, n_seg=n_seg,
                devices=devices, rows_padded=1 << 20, **more)


@pytest.mark.parametrize("c,want", [
    (call(devices=1), 0),
    (call(devices=4), 2 * 3 * 128 * 2 * 5 * 4),
    (call(reducer="quantile", metrics=1, devices=4),
     2 * 3 * 128 * 1 * 384 * 4),
    (call(reducer="quantile", metrics=3, n_seg=256, devices=2),
     2 * 1 * 256 * 3 * 384 * 4),
])
def test_collective_bytes(c, want):
    assert exchange.collective_bytes(c) == want


def test_table_bytes_by_reducer_width():
    assert exchange.table_bytes(call()) == 128 * 2 * 5 * 4
    assert exchange.table_bytes(call(reducer="quantile")) == \
        128 * 2 * 384 * 4


def test_exchange_ns_counts_the_collectives_and_min_max_ops():
    trace = {"by_op_ns": {"all-reduce f32[2,1024,3]": 30e3,
                          "all-gather.4 f32[2,1024,3]": 20e3,
                          "pmin.7 f32[2,1024,1]": 5e3,
                          "pmax.7 f32[2,1024,1]": 5e3,
                          "reduce_scatter.7": 1e3,
                          "fusion.3 f32[2,128]": 900e3}}
    assert exchange.exchange_ns(trace) == 61e3


MOMENTS = call(exchange_bytes=128 * 2 * 5 * 4)
SKETCH = call(reducer="quantile", metrics=1,
              exchange_bytes=128 * 1 * 384 * 4)
OPS = {"all-reduce f32[2,1024,3]": 40e3, "all-gather.4 f32[2,1024,3]": 20e3,
       "fusion.3 f32[2,128]": 900e3}


def reading(monkeypatch, name, calls, ops=OPS, cold=4, red=True):
    """``name``'s reading over a window whose dispatch spans carry
    ``calls`` and whose device ops took ``ops``."""
    events = {"repro.reduce.dispatch": [(0.0, 1.0, c) for c in calls]}
    monkeypatch.setattr(program_spans, "program", lambda ctx: (
        {"events": events, "names": {}} if red else None))
    ctx = types.SimpleNamespace(
        trace={"by_op_ns": dict(ops)}, device_kind="TPU v5 lite",
        done=[{"cache_hit": False, "inflight_hit": False}] * cold
        + [{"cache_hit": True, "inflight_hit": False}])
    return run.metric_reader(name)(ctx)


def test_exchange_mb_reads_the_stat_per_cold_query(monkeypatch):
    got = reading(monkeypatch, "exchange_mb.cold", [MOMENTS, SKETCH])
    want = (MOMENTS["exchange_bytes"] + SKETCH["exchange_bytes"]) / 1e6 / 4
    assert got == pytest.approx(want)


def test_collective_roofline_reads_link_bytes_over_op_time(monkeypatch):
    got = reading(monkeypatch, "collective_roofline.cold",
                  [MOMENTS, SKETCH])
    moved = (exchange.collective_bytes(MOMENTS)
             + exchange.collective_bytes(SKETCH))
    assert got == pytest.approx(100.0 * moved / (60e3 / 1e9 * ICI))
    assert 0 < got <= 100


@pytest.mark.parametrize("name", ["exchange_mb.cold",
                                  "collective_roofline.cold"])
@pytest.mark.parametrize("case", ["no_trace", "one_chip", "no_dispatch"])
def test_readers_find_nothing(monkeypatch, name, case):
    one = call(devices=1, exchange_bytes=0)
    kw = {"no_trace": dict(calls=[MOMENTS], red=False),
          "one_chip": dict(calls=[one], ops={"fusion.3": 900e3}),
          "no_dispatch": dict(calls=[])}[case]
    assert reading(monkeypatch, name, **kw) is None


def test_exchange_mb_finds_nothing_without_the_stat(monkeypatch):
    """A program that sets no exchange_bytes (the stat is new)."""
    bare = {k: v for k, v in MOMENTS.items() if k != "exchange_bytes"}
    assert reading(monkeypatch, "exchange_mb.cold", [bare]) is None


def test_collective_roofline_finds_nothing_without_collective_ops(
        monkeypatch):
    assert reading(monkeypatch, "collective_roofline.cold", [MOMENTS],
                   ops={"fusion.3": 900e3}) is None


SCRIPT = r"""
import json, sys, tempfile
sys.path.insert(0, {here!r})
import conftest, run, trace_reduce
trace_reduce.PEAKS["cpu"] = dict(trace_reduce.PEAKS["TPU v5 lite"],
                                 source="test only")
ctx = conftest.tiny(run.load_cell(run.load_benchmark(),
                                  "paper8.explore_cold"),
                    kernels=1500, memcpys=200)
with tempfile.TemporaryDirectory() as work:
    res = run.run_explore(ctx, 2**33 + 9, 2.0, True, work,
                          require_tpu=False)
print(json.dumps(res))
"""


def test_traced_four_device_run_reports_the_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT.format(here=HERE)],
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["device"]["count"] == 4
    m = res["metrics"]
    assert m["exchange_mb.cold"]["value"] > 0
    assert 0 < m["collective_roofline.cold"]["value"]
