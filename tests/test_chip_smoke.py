"""chip_smoke.py on the CPU: its query-and-compare phases at a tiny size
(the test picks the devices; the script itself has no CPU mode), its
comparisons catching a wrong answer, and its refusal to run without a
TPU."""

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.core import SyntheticSpec  # noqa: E402

TINY = dict(n_ranks=4, kernels_per_rank=3000, memcpys_per_rank=400,
            duration_s=30.0, seed=3)


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    work = tmp_path_factory.mktemp("smoke")
    store = chip_smoke.build_store(work, 3, SyntheticSpec(**TINY))
    ref, dev = chip_smoke.query_and_compare(store, jax.devices()[:1],
                                            http=True)
    return store, ref, dev


def test_query_and_compare_phase_on_cpu(smoke_run):
    _, ref, dev = smoke_run
    assert len(ref) == len(dev) == len(chip_smoke.smoke_queries(
        smoke_run[0]))
    assert all(d.rows_scanned > 0 and not d.cache_hit for d in dev)


@pytest.mark.parametrize("field,delta", [("count", 1.0), ("sum", 1e-3),
                                         ("max", 1.0)])
def test_compare_catches_a_wrong_device_answer(smoke_run, field, delta):
    _, ref, dev = smoke_run
    d = dev[0]
    grouped = dataclasses.replace(d.result.grouped)
    arr = getattr(grouped, field).copy()
    i = np.argwhere(ref[0].result.grouped.count > 0)[0]
    arr[tuple(i)] += delta * max(abs(arr[tuple(i)]), 1.0)
    setattr(grouped, field, arr)
    bad = dataclasses.replace(
        d, result=dataclasses.replace(d.result, grouped=grouped))
    with pytest.raises(AssertionError):
        chip_smoke.compare_to_host("mutated", bad, ref[0])


def test_four_device_phase_on_cpu_devices(tmp_path):
    """The --chips 4 phase on four virtual CPU devices (subprocess, so
    this process keeps its one-device view)."""
    code = textwrap.dedent(f"""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import sys
    from pathlib import Path
    sys.path.insert(0, {str(ROOT)!r})
    import jax
    import chip_smoke
    from repro.core import SyntheticSpec
    assert len(jax.devices()) == 4
    store = chip_smoke.build_store(Path({str(tmp_path)!r}), 3,
                                   SyntheticSpec(**{TINY!r}))
    chip_smoke.four_chip_phase(store, jax.devices())
    print('OK')
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-2000:], out.stderr[-3000:])
    assert "devices=[0, 1, 2, 3]" in out.stdout


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=str(ROOT))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr
