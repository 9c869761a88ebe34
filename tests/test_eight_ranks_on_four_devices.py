"""An eight-rank capture (two 4-GPU nodes) served on a four-device mesh,
at a tiny size: cold queries over ``POST /v1/query`` and through
``VariabilityPipeline.query`` on the jax backend, against the serial
float64 host path, and the ``exchange_bytes`` stat of the reduce
dispatches on four devices and on one. The whole run is one process of
its own, which the device count has to be set for before JAX starts; the
tests read its report."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent(r"""
    import json, sys
    from pathlib import Path
    sys.path.insert(0, {root!r})
    import jax
    import chip_smoke
    from repro.core import (PipelineConfig, Query, SyntheticSpec,
                            TraceStore, VariabilityPipeline)
    from repro.core.aggregation import DEVICE_DISPATCHES
    from repro.core.spans import TOTALS
    from repro.serve import QueryClient
    from repro.serve.query_service import _render_result

    N_RANKS, SEED = 8, 2**33 + 11
    devs = jax.devices()
    assert len(devs) == 4, devs
    store = chip_smoke.build_store(Path({work!r}), SEED, SyntheticSpec(
        n_ranks=N_RANKS, kernels_per_rank=1500, memcpys_per_rank=200,
        duration_s=16.0, seed=SEED))
    man = TraceStore(store).read_manifest()
    t0, span = int(man.t_start), int(man.t_end - man.t_start)
    queries = [
        Query(metrics=("k_stall", "m_duration"), group_by="k_device"),
        Query(metrics=("k_stall",), group_by="k_name",
              reducers=("moments", "quantile"), anomaly_score="p99"),
        Query(metrics=("m_bytes",), ranks=(1, 5, 6), group_by="src_rank"),
        Query(metrics=("m_duration", "k_stall"), transfer_kinds=(1, 2),
              time_window=(t0 + span // 4, t0 + 3 * span // 4),
              group_by="m_kind"),
        Query(metrics=("k_stall",), ranks=(0, 7), anomaly_score="p99",
              reducers=("moments", "quantile")),
    ]
    ref = VariabilityPipeline(PipelineConfig(
        n_ranks=N_RANKS, backend="serial")).query(store, queries)
    exact = [chip_smoke.exact_quantiles(store, q, r.result)
             if "quantile" in q.canonical_reducers else None
             for q, r in zip(queries, ref)]


    def checked(fn):
        try:
            fn()
            return ""
        except AssertionError as e:
            return str(e)


    def stat(name, key):
        rec = TOTALS.snapshot().get(name, {{}})
        return rec.get("count", 0), rec.get("stats", {{}}).get(key, 0)


    def served(devices):
        chip_smoke.clear_caches(store)
        DEVICE_DISPATCHES.clear()
        n0, x0 = stat("repro.reduce.dispatch", "exchange_bytes")
        _, b0 = stat("repro.reduce.d2h", "bytes")
        svc = VariabilityPipeline(PipelineConfig(
            n_ranks=N_RANKS, backend="jax", devices=list(devices))).serve(
            store, port=0)
        try:
            client = QueryClient(port=svc.cfg.port, timeout_s=300.0)
            assert client.wait_healthy(timeout_s=30.0)
            results = client.query_raw(queries)["results"]
        finally:
            svc.stop()
        n1, x1 = stat("repro.reduce.dispatch", "exchange_bytes")
        _, b1 = stat("repro.reduce.d2h", "bytes")

        def compare():
            for i, (got, r) in enumerate(zip(results, ref)):
                assert not got["cache_hit"], got["provenance"]
                chip_smoke.compare_rendered(
                    f"served query {{i}}", got, _render_result(r),
                    float(chip_smoke.sum_rtol(
                        r.result.grouped.count.max())))
        return {{"answers": checked(compare),
                 "placement": checked(
                     lambda: chip_smoke.check_placement(devices, queries)),
                 "dispatches": n1 - n0, "exchange_bytes": x1 - x0,
                 "d2h_bytes": b1 - b0}}


    def pipeline():
        chip_smoke.clear_caches(store)
        out = VariabilityPipeline(PipelineConfig(
            n_ranks=N_RANKS, backend="jax", devices=devs)).query(
            store, queries)
        for i, (d, r, e) in enumerate(zip(out, ref, exact)):
            assert d.rows_scanned > 0 and not d.cache_hit
            chip_smoke.compare_to_host(f"pipeline query {{i}}", d, r, e)


    report = {{"four": served(devs), "one": served(devs[:1]),
               "pipeline": checked(pipeline)}}
    print(json.dumps(report))
""")


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    work = tmp_path_factory.mktemp("eight_ranks")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=str(ROOT),
                                             work=str(work))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("devices", ["four", "one"])
def test_served_answers_match_the_host_path(report, devices):
    got = report[devices]
    assert got["dispatches"] > 0
    assert got["answers"] == ""
    assert got["placement"] == ""


def test_pipeline_answers_match_the_host_path_with_p99(report):
    assert report["pipeline"] == ""


def test_exchange_bytes_is_the_table_on_four_devices(report):
    four = report["four"]
    # each dispatch's table, the bytes copied back from one device
    assert four["exchange_bytes"] == four["d2h_bytes"] > 0


def test_exchange_bytes_is_zero_on_one_device(report):
    one = report["one"]
    assert one["d2h_bytes"] > 0
    assert one["exchange_bytes"] == 0
