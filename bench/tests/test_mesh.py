"""The four-chip cell on four virtual CPU devices: a whole run, and the
fault of the exchange between chips left out. Each runs in a process of
its own, which the device count has to be set for before JAX starts."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

SCRIPT = r"""
import json, os, sys, tempfile
sys.path.insert(0, {here!r})
import conftest, run
ctx = conftest.tiny(run.load_cell(conftest.benchmark_with_candidates(),
                                  "paper8.explore_cold"),
                    kernels=1500, memcpys=200)
if {fault!r}:
    from repro.core import distributed
    distributed._collaborative_reduce = lambda local, axis, size: local
    distributed._collaborative_sum = lambda vals, axis, size, dim: vals
with tempfile.TemporaryDirectory() as work:
    res = run.run_explore(ctx, 2**33 + 5, 2.0, False, work,
                          require_tpu=False)
print(json.dumps(res))
"""


def run_on_four(fault: bool):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(here=HERE, fault=fault)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_device_run():
    res = run_on_four(fault=False)
    assert res["correct"] is True
    assert res["device"]["count"] == 4


def test_fault_exchange_between_chips_left_out():
    res = run_on_four(fault=True)
    assert res["correct"] is False
    assert res["compared"]["count_mismatch"]["value"] > 0
