"""Roofline machinery: walker exactness on scans (the cost_analysis gap),
collective parsing, wire factors, model-flops bookkeeping."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline import (PEAK_FLOPS, Roofline, active_param_count,
                            model_flops_for, parse_collectives)
from repro.roofline.hlo_cost import analyze_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_cost_analysis_undercounts_scans_and_walker_fixes_it():
    """Documents the XLA behaviour the walker exists for."""
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 256), jnp.float32)

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return y
    c = _compile(f, x, w)
    expected = 2 * 8 * 256 * 256 * 12
    ca = c.cost_analysis().get("flops", 0)
    assert ca < expected / 2                  # the gap
    walked = analyze_hlo(c.as_text(), 1)
    np.testing.assert_allclose(walked.flops, expected, rtol=1e-6)


def test_walker_nested_scan_multiplies():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 128), jnp.float32)

    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y
    c = _compile(f, x, w)
    walked = analyze_hlo(c.as_text(), 1)
    np.testing.assert_allclose(walked.flops, 2 * 4 * 128 * 128 * 15,
                               rtol=1e-6)


def test_walker_counts_unrolled_exactly():
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)

    def f(x, w):
        for _ in range(7):
            x = x @ w
        return x
    walked = analyze_hlo(_compile(f, x, w).as_text(), 1)
    np.testing.assert_allclose(walked.flops, 2 * 4 * 64 * 64 * 7,
                               rtol=1e-6)


def test_collective_parse_and_wire_factors(tmp_path):
    import subprocess, sys, textwrap, os
    code = textwrap.dedent("""
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
        import jax, jax.numpy as jnp, sys
        sys.path.insert(0, %r)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.sharding import AxisType
        from repro.roofline import parse_collectives
        mesh = jax.make_mesh((2,4), ('data','model'),
                             axis_types=(AxisType.Auto,) * 2)
        x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
        w1 = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
        w2 = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
        s = lambda *p: NamedSharding(mesh, P(*p))
        f = jax.jit(lambda a,b,c: jax.nn.relu(a@b)@c,
                    in_shardings=(s('data',None), s(None,'model'),
                                  s('model',None)),
                    out_shardings=s('data',None))
        comp = f.lower(x,w1,w2).compile()
        st = parse_collectives(comp.as_text(), 8)
        assert st.count.get('all-reduce', 0) >= 1, st.count
        assert st.result_bytes['all-reduce'] == 65536, st.result_bytes
        assert abs(st.wire_bytes - 65536*2*3/4) < 1, st.wire_bytes
        print('OK')
    """) % (os.path.join(os.path.dirname(__file__), "..", "src"),)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]


def test_roofline_terms_and_dominance():
    r = Roofline(arch="a", shape="s", mesh="m", chips=256,
                 flops_per_dev=197e12, bytes_per_dev=819e9 * 2,
                 wire_bytes_per_dev=50e9 * 0.5,
                 model_flops=197e12 * 256 * 0.5, collectives={})
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 2.0) < 1e-9
    assert abs(r.collective_s - 0.5) < 1e-9
    assert r.dominant == "memory"
    assert abs(r.step_s - 2.0) < 1e-9
    assert abs(r.useful_ratio - 0.5) < 1e-9


def test_model_flops_conventions():
    assert model_flops_for("train", 100, 10) == 6000
    assert model_flops_for("prefill", 100, 10) == 2000
    assert model_flops_for("decode", 100, 10) == 2000


def test_active_params_moe_scaling():
    import jax
    tree = {"segments": {"0": {
        "moe": {"experts": {"w_up": jax.ShapeDtypeStruct((8, 4, 4),
                                                         jnp.float32)}},
        "attn": {"wq": jax.ShapeDtypeStruct((4, 4, 4), jnp.float32)}}}}
    total, act = active_param_count(tree, top_k=2, n_experts=8)
    assert total == 8 * 16 + 64
    assert act == 2 * 16 + 64
