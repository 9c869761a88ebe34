"""A whole run at a tiny size on the CPU (the harness's look for a chip
skipped), its result line, the CLI's refusal without a TPU, the control,
and the faults the comparison has to catch."""

import json
import os
import subprocess
import sys

import pytest

import control
import reference
import run
import trace_reduce

SEED = 2**33 + 17
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_tiny(ctx, tmp_path, trace=False, seconds=2.0, seed=SEED):
    work = tmp_path / "work"
    work.mkdir(exist_ok=True)
    return run.run_explore(ctx, seed, seconds, trace, str(work),
                           require_tpu=False)


def test_result_line(cell, tmp_path):
    res = run_tiny(cell("paper4.explore_cold"), tmp_path)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    dev = res["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert set(res["compared"]) == set(reference.LIMITS)
    json.dumps(res)


def test_traced_result_line(cell, tmp_path, monkeypatch):
    monkeypatch.setitem(trace_reduce.PEAKS, "cpu",
                        {"hbm_bytes_per_s": 1e12, "source": "test only"})
    res = run_tiny(cell("paper4.explore_cold"), tmp_path, trace=True)
    assert res["correct"] is True
    assert list(res)[-1] == "compared"
    got = set(res["metrics"])
    assert {"query_p50_ms.cold", "fused_width.cold",
            "summary_hit_share.cold", "host_prep_ms.cold",
            "compiles_in_window.cold", "device_idle_share.cold",
            "reduce_roofline.cold"} <= got
    assert not {"queries_per_s", "setup_s"} & got
    assert 0 < res["metrics"]["reduce_roofline.cold"]["value"] <= 100
    assert res["metrics"]["host_prep_ms.cold"]["value"] > 0
    assert res["device"]["busy_s"] > 0
    assert res["device"]["window_s"] >= 2.0
    b = res["breakdown"]
    assert b["device_ops"] and len(b["idle_gaps"]) <= 10


def test_hot_cell(cell, tmp_path):
    res = run_tiny(cell("paper4.explore_hot"), tmp_path)
    assert res["correct"] is True and res["attempted"] > 50
    m = res["metrics"]
    assert set(m) == {"query_p50_ms", "setup_s"}
    assert m["query_p50_ms"]["value"] > 0
    traced = run_tiny(cell("paper4.explore_hot"), tmp_path, trace=True)
    assert traced["correct"] is True
    t = traced["metrics"]
    assert {"query_p90_ms.hot", "fused_width.hot", "summary_hit_share.hot",
            "compiles_in_window.hot", "device_idle_share.hot"} <= set(t)
    assert t["query_p90_ms.hot"]["value"] > 0
    assert t["summary_hit_share.hot"]["value"] > 50


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "paper4.explore_cold", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_refuses_a_cpu_device(cell, tmp_path):
    work = tmp_path / "work"
    work.mkdir()
    with pytest.raises(run.NoChip):
        run.run_explore(cell("paper4.explore_cold", kernels=500,
                             memcpys=60), SEED, 1.0, False, str(work))


def test_bfloat16_control_is_not_correct(cell):
    for name in ("paper4.explore_cold", "paper4.explore_hot"):
        out = control.control_numbers(cell(name), SEED, per_session=3)
        assert out["correct"] is False
        assert out["numbers"]["minmax_mismatch"] > 0
        assert out["numbers"]["mean_gap"] > reference.LIMITS["mean_gap"]


# --- faults of the timed path: each must make `correct` false ----------------

def test_fault_half_the_rows_left_out(cell, tmp_path, monkeypatch):
    from repro.core import aggregation
    orig = aggregation._slotwise_device_partition

    def half(counts, n_dev):
        row, valid = orig(counts, n_dev)
        valid = valid.copy()
        live = valid.nonzero()[0]
        valid[live[len(live) // 2:]] = False
        return row, valid
    monkeypatch.setattr(aggregation, "_slotwise_device_partition", half)
    res = run_tiny(cell("paper4.explore_cold"), tmp_path)
    assert res["correct"] is False
    assert res["compared"]["count_mismatch"]["value"] > 0


def test_fault_answer_altered(cell, tmp_path, monkeypatch):
    from repro.serve import query_service
    orig = query_service._render_result

    def altered(qr):
        out = orig(qr)
        for per in out["groups"].values():
            for m in per.values():
                m["mean"] *= 1.0 + 2.0 ** -12
            break
        return out
    monkeypatch.setattr(query_service, "_render_result", altered)
    res = run_tiny(cell("paper4.explore_cold"), tmp_path)
    assert res["correct"] is False


def test_fault_state_unchanged(cell, tmp_path, monkeypatch):
    """Every answer is the first one the service gave: the served state
    never moves past it."""
    from repro.serve import query_service
    orig = query_service._render_result
    first = []

    def stale(qr):
        out = orig(qr)
        if not first:
            first.append(out)
        return dict(first[0], query=out["query"])
    monkeypatch.setattr(query_service, "_render_result", stale)
    res = run_tiny(cell("paper4.explore_cold"), tmp_path)
    assert res["correct"] is False
