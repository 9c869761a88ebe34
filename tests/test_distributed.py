"""Multi-device semantics (8 fake host devices via subprocess, so the main
pytest process keeps its single-device view): shard_map analyzer ≡ serial,
MoE EP ≡ local, sharded train step ≡ unsharded, cache specs legal."""

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, timeout=560):
    full = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys\n"
            f"sys.path.insert(0, {SRC!r})\n"
            "import jax\n"
            "from jax.sharding import AxisType\n"
            "def make_mesh(shape, names):\n"
            "    return jax.make_mesh(shape, names,\n"
            "        axis_types=(AxisType.Auto,) * len(shape))\n"
            + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", full],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-1000:], out.stderr[-3000:])


def test_distributed_binstats_equals_serial():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed import (binstats_local,
                                        distributed_binstats)
    rng = np.random.default_rng(0)
    n, n_bins, total = 4096, 64, 1e9
    ts = jnp.asarray(rng.uniform(0, total, n), jnp.float32)
    vals = jnp.asarray(rng.normal(10, 3, n), jnp.float32)
    mesh = make_mesh((8,), ('data',))
    dist = distributed_binstats(ts, vals, total, n_bins, mesh)
    inv = np.float32(n_bins / total)
    bins = jnp.clip((ts * inv).astype(jnp.int32), 0, n_bins - 1)
    ser = binstats_local(bins, vals, n_bins)
    np.testing.assert_allclose(np.asarray(dist)[:, :3],
                               np.asarray(ser)[:, :3], rtol=1e-4,
                               atol=1e-2)
    np.testing.assert_allclose(np.asarray(dist)[:, 3:],
                               np.asarray(ser)[:, 3:], rtol=1e-5)
    print('OK')
    """)


def test_moe_ep_and_replicated_equal_local():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.moe import MoEConfig, moe_init, moe_forward
    from repro.models.shardrules import make_ctx
    cfg = MoEConfig(d_model=32, d_ff=16, n_experts=8, top_k=2,
                    n_shared=1, capacity_factor=2.0)
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16, 32)),
                    jnp.float32)
    out_l, _ = moe_forward(params, x, cfg, None)
    mesh = make_mesh((2, 4), ('data', 'model'))
    ctx = make_ctx(mesh)
    with jax.set_mesh(mesh):
        out_ep, _ = moe_forward(params, x, cfg, ctx)
        out_rep, _ = moe_forward(params, x[:, :1], cfg, ctx)
    out_lr, _ = moe_forward(params, x[:, :1], cfg, None)
    np.testing.assert_allclose(np.asarray(out_l), np.asarray(out_ep),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_lr), np.asarray(out_rep),
                               rtol=1e-4, atol=1e-4)
    print('OK')
    """)


def test_sharded_train_step_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataConfig, make_batch
    from repro.train.step import (TrainConfig, init_state,
                                  make_train_step, state_specs,
                                  batch_specs, to_named)
    cfg = get_smoke_config('granite-moe-1b-a400m')
    tcfg = TrainConfig()
    state = init_state(cfg, jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        cfg, DataConfig(batch=8, seq=16), 0).items()}
    # single device reference
    s_ref, m_ref = make_train_step(cfg, tcfg, None)(
        jax.tree.map(lambda x: x, state), batch)
    # 2x4 mesh
    mesh = make_mesh((2, 4), ('data', 'model'))
    sspec = to_named(state_specs(state, mesh), mesh)
    bspec = to_named(batch_specs(batch, mesh), mesh)
    step = jax.jit(make_train_step(cfg, tcfg, mesh),
                   in_shardings=(sspec, bspec), out_shardings=(sspec, None))
    with jax.set_mesh(mesh):
        s_sh, m_sh = step(state, batch)
    np.testing.assert_allclose(float(m_ref['loss']), float(m_sh['loss']),
                               rtol=2e-3)
    a = np.asarray(s_ref['params']['final_norm']['scale'])
    b = np.asarray(s_sh['params']['final_norm']['scale'])
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
    print('OK')
    """)


def test_serve_cache_specs_are_legal_shardings():
    _run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models.model import init_cache
    from repro.serve.engine import cache_specs
    from jax.sharding import NamedSharding
    mesh = make_mesh((2, 4), ('data', 'model'))
    for arch in ('hymba-1.5b', 'deepseek-v2-236b', 'mamba2-370m',
                 'h2o-danube-1.8b'):
        cfg = get_smoke_config(arch)
        caches = jax.eval_shape(lambda c=cfg: init_cache(c, 8, 64))
        specs = cache_specs(cfg, caches, mesh)
        jax.tree.map(lambda x, s: NamedSharding(mesh, s), caches, specs)
    print('OK')
    """)


def test_multipod_mesh_axes():
    _run("""
    from repro.models.shardrules import batch_axes, spec_for
    mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
    assert batch_axes(mesh) == ('pod', 'data')
    s = spec_for('segments/0/ffn/w_up', (4, 64, 128), mesh)
    assert s[1] == ('pod', 'data') and s[2] in ('model', ('model',)), s
    # non-divisible head dim falls back to replication
    s2 = spec_for('segments/0/attn/wq', (4, 64, 25, 8), mesh)
    assert s2[2] is None, s2
    print('OK')
    """)


def test_elastic_checkpoint_reshard_across_meshes(tmp_path):
    """Fault-tolerance: a checkpoint written from an 8-device (2,4) mesh
    restores onto a 4-device (2,2) mesh (elastic downscale) and the train
    step keeps producing the same loss."""
    _run("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataConfig, make_batch
    from repro.models.shardrules import tree_shardings
    from repro.train import CheckpointManager
    from repro.train.step import (TrainConfig, init_state,
                                  make_train_step, state_specs,
                                  batch_specs, to_named)
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_smoke_config('granite-moe-1b-a400m')
    tcfg = TrainConfig()
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        cfg, DataConfig(batch=8, seq=16), 0).items()}
    d = tempfile.mkdtemp()

    def mesh_of(shape):
        return make_mesh(shape, ('data', 'model'))

    # train 2 steps on the 8-device mesh, checkpoint
    mesh8 = mesh_of((2, 4))
    state = init_state(cfg, jax.random.PRNGKey(0))
    sspec8 = to_named(state_specs(state, mesh8), mesh8)
    step8 = jax.jit(make_train_step(cfg, tcfg, mesh8),
                    in_shardings=(sspec8, to_named(
                        batch_specs(batch, mesh8), mesh8)),
                    out_shardings=(sspec8, None))
    with jax.set_mesh(mesh8):
        state, _ = step8(state, batch)
        state, m8 = step8(state, batch)
    mgr = CheckpointManager(d)
    mgr.save(state, 2)

    # restore onto a 4-device mesh (different sharding layout)
    mesh4 = mesh_of((2, 2))
    template = jax.eval_shape(
        lambda: init_state(cfg, jax.random.PRNGKey(0)))
    sh4 = {'step': NamedSharding(mesh4, P()),
           'params': tree_shardings(template['params'], mesh4),
           'opt': {'m': tree_shardings(template['opt']['m'], mesh4),
                   'v': tree_shardings(template['opt']['v'], mesh4)}}
    restored = mgr.restore(template, shardings=sh4)
    assert int(restored['step']) == 2
    sspec4 = to_named(state_specs(restored, mesh4), mesh4)
    step4 = jax.jit(make_train_step(cfg, tcfg, mesh4),
                    in_shardings=(sspec4, to_named(
                        batch_specs(batch, mesh4), mesh4)),
                    out_shardings=(sspec4, None))
    with jax.set_mesh(mesh4):
        _, m4 = step4(restored, batch)
    # the 3rd-step loss on the downscaled mesh matches the 8-device run
    with jax.set_mesh(mesh8):
        _, m8b = step8(state, batch)
    np.testing.assert_allclose(float(m4['loss']), float(m8b['loss']),
                               rtol=2e-3)
    print('OK')
    """)
