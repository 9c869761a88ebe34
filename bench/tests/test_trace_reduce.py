"""The trace reduction, on a small trace recorded on the CPU."""

import time

import pytest

import trace_reduce as tr


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
        with TraceAnnotation("bench.scan_prep"):
            time.sleep(0.05)
            with TraceAnnotation("bench.read_shard"):
                time.sleep(0.02)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    return tr.load(tr.find_xplane(d))


def test_reduce_cpu_trace(cpu_trace):
    red = tr.reduce(cpu_trace)
    assert red["n_devices"] >= 1
    assert 0 < red["busy_ns"] < red["window_ns"]
    assert red["window_ns"] >= 70e6
    assert sum(red["by_op_ns"].values()) > 0
    assert any("lambda" in k for k in red["by_module_ns"])
    # nested spans: the outer one's self time leaves the inner one out
    assert 45e6 <= red["self_ns"]["bench.scan_prep"] < 65e6
    assert 18e6 <= red["self_ns"]["bench.read_shard"] < 35e6
    # the longest idle gap is the host sleeping in the outer span
    who, secs = red["idle_gaps"][0]
    assert who == "bench.scan_prep" and secs >= 0.06
    b = tr.breakdown(red)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(v, float) for _, v in b["device_ops"])


def test_intervals():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                       (7, 10)]
    assert tr.gaps([(1, 2)], 0, 4) == [(0, 1), (2, 4)]
    assert tr.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def test_self_times_by_thread():
    spans = [(0, 10, "bench.a", "t1"), (2, 5, "bench.b", "t1"),
             (3, 4, "bench.c", "t1"), (0, 8, "bench.b", "t2")]
    st = tr.self_times(spans, 0, 100)
    assert st == {"bench.a": 7.0, "bench.b": 10.0, "bench.c": 1.0}


def test_peaks_are_keyed_by_device_kind():
    assert tr.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "source" in tr.peaks_for("TPU v5 lite")
    with pytest.raises(KeyError):
        tr.peaks_for("cpu")


def test_op_name():
    assert tr.op_name("%fusion.3 = f32[3,128]{0,1:T(8,128)S(1)} fusion("
                      "s32[2097152]{0:T(1024)} %copy-done.2)") == \
        "fusion.3 f32[3,128]"
    assert tr.op_name("%all-reduce.1 = f32[640,3]{1,0} all-reduce(x)") \
        .startswith(tr.COLLECTIVE_PREFIXES)
    assert tr.op_name("wrapped_sine") == "wrapped_sine"
