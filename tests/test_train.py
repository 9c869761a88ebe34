"""Training-stack tests: optimizer math, grad-accum equivalence, loss
descent, checkpoint round-trip + elastic restore, auto-resume."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, make_batch
from repro.models.model import init_params, loss_fn
from repro.train import (CheckpointManager, RunConfig, TrainConfig,
                         Trainer, init_state, make_train_step)
from repro.train.optim import (AdamWConfig, adamw_init, adamw_update,
                               cosine_lr, global_norm)


def test_adamw_matches_reference_math():
    cfg = AdamWConfig(peak_lr=0.1, warmup_steps=0, total_steps=100,
                      weight_decay=0.0, clip_norm=0.0, b1=0.9, b2=0.99)
    params = {"w": jnp.asarray([[1.0, 2.0], [3.0, 4.0]])}
    grads = {"w": jnp.asarray([[0.1, -0.2], [0.3, 0.4]])}
    state = adamw_init(params)
    new_p, new_s, stats = adamw_update(cfg, grads, state, params,
                                       jnp.int32(0))
    g = np.asarray(grads["w"])
    m = 0.1 * g
    v = 0.01 * g * g
    upd = (m / (1 - 0.9)) / (np.sqrt(v / (1 - 0.99)) + cfg.eps)
    lr = float(cosine_lr(cfg, jnp.int32(0)))
    np.testing.assert_allclose(np.asarray(new_p["w"]),
                               np.asarray(params["w"]) - lr * upd,
                               rtol=1e-5)


def test_cosine_schedule_shape():
    cfg = AdamWConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    assert float(cosine_lr(cfg, jnp.int32(0))) == 0.0
    assert abs(float(cosine_lr(cfg, jnp.int32(10))) - 1.0) < 1e-6
    assert abs(float(cosine_lr(cfg, jnp.int32(100))) - 0.1) < 1e-3
    assert float(cosine_lr(cfg, jnp.int32(55))) < 1.0


def test_grad_clipping_bounds_update():
    cfg = AdamWConfig(clip_norm=1e-3, weight_decay=0.0)
    params = {"w": jnp.ones((4, 4))}
    grads = {"w": 1e6 * jnp.ones((4, 4))}
    state = adamw_init(params)
    new_p, _, stats = adamw_update(cfg, grads, state, params, jnp.int32(0))
    assert float(stats["grad_norm"]) > 1e5
    assert np.all(np.abs(np.asarray(new_p["w"] - params["w"])) < 1.0)


def test_grad_accum_equivalence():
    """accum=2 over a batch == accum=1 over the same batch (loss average
    and near-identical update)."""
    cfg = get_smoke_config("stablelm-3b")
    state1 = init_state(cfg, jax.random.PRNGKey(0))
    state2 = jax.tree.map(lambda x: x, state1)
    dcfg = DataConfig(batch=4, seq=16)
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(cfg, dcfg, step=0).items()}
    s1 = make_train_step(cfg, TrainConfig(grad_accum=1))
    s2 = make_train_step(cfg, TrainConfig(grad_accum=2))
    new1, m1 = s1(state1, batch)
    new2, m2 = s2(state2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)
    d1 = np.asarray(new1["params"]["final_norm"]["scale"])
    d2 = np.asarray(new2["params"]["final_norm"]["scale"])
    np.testing.assert_allclose(d1, d2, rtol=1e-3, atol=1e-4)


def test_loss_decreases_multiple_archs(tmp_path):
    # Formerly quarantined: hymba went NaN at ~step 12 because the SSD
    # scan's non-causal decay exponents (li > 0, growing with trained dt)
    # overflowed exp to +inf and the masking where's backward turned that
    # into 0·inf = NaN. Fixed by masking li before exp (ssm.py ssd_scan).
    for arch in ("mamba2-370m", "hymba-1.5b"):
        cfg = get_smoke_config(arch)
        tcfg = TrainConfig(optim=AdamWConfig(
            peak_lr=5e-3, warmup_steps=3, total_steps=30,
            weight_decay=0.0))
        dcfg = DataConfig(batch=4, seq=24)
        rcfg = RunConfig(steps=25, ckpt_every=100, monitor_every=100,
                         workdir=str(tmp_path / arch))
        res = Trainer(cfg, tcfg, dcfg, rcfg).run()
        ls = res["losses"]
        assert np.mean(ls[-5:]) < np.mean(ls[:5]), \
            f"{arch} loss did not decrease: {ls[:3]} -> {ls[-3:]}"


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_smoke_config("granite-moe-1b-a400m")
    state = init_state(cfg, jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=2)
    mgr.save(state, 7)
    restored = mgr.restore(jax.eval_shape(lambda: state))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_last_k(tmp_path):
    cfg = get_smoke_config("mamba2-370m")
    state = init_state(cfg, jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(state, s)
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    cfg = get_smoke_config("mamba2-370m")
    state = init_state(cfg, jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(state, 1)
    bad = jax.eval_shape(lambda: {
        **state, "step": jnp.zeros((3,), jnp.int32)})
    with pytest.raises(ValueError):
        mgr.restore(bad)


def test_async_checkpoint_and_resume(tmp_path):
    cfg = get_smoke_config("stablelm-3b")
    state = init_state(cfg, jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(state, 5, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 5
    # elastic restore path: placement with explicit shardings (1-device)
    from repro.models.shardrules import tree_shardings
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = {"step": jax.sharding.NamedSharding(
              mesh, jax.sharding.PartitionSpec()),
          "params": tree_shardings(state["params"], mesh),
          "opt": {"m": tree_shardings(state["opt"]["m"], mesh),
                  "v": tree_shardings(state["opt"]["v"], mesh)}}
    restored = mgr.restore(jax.eval_shape(lambda: state), shardings=sh)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["final_norm"]["scale"]),
        np.asarray(state["params"]["final_norm"]["scale"]))


def test_data_pipeline_determinism_and_hostsharding():
    cfg = get_smoke_config("h2o-danube-1.8b")
    dcfg = DataConfig(batch=8, seq=16, seed=5)
    a = make_batch(cfg, dcfg, step=3)
    b = make_batch(cfg, dcfg, step=3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = make_batch(cfg, dcfg, step=4)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # host sharding: two hosts produce disjoint slices deterministically
    h0 = make_batch(cfg, dcfg, step=3, host=0, n_hosts=2)
    h1 = make_batch(cfg, dcfg, step=3, host=1, n_hosts=2)
    assert h0["tokens"].shape[0] == 4
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_labels_are_shifted_tokens():
    cfg = get_smoke_config("stablelm-3b")
    b = make_batch(cfg, DataConfig(batch=2, seq=16), step=0)
    # pipeline contract: labels[t] == the next token after tokens[t]
    assert b["tokens"].shape == b["labels"].shape
    # regenerate the unshifted stream to verify
    from repro.data.pipeline import _lm_tokens, _rng
    toks = _lm_tokens(_rng(DataConfig(batch=2, seq=16), 0, 0), 2, 16,
                      cfg.vocab)
    np.testing.assert_array_equal(b["tokens"], toks[:, :-1])
    np.testing.assert_array_equal(b["labels"], toks[:, 1:])
