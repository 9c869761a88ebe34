"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) ≡ ref.py oracle
≡ the numpy aggregation path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:           # degrade property sweeps to skips
    HAVE_HYPOTHESIS = False

from repro.core.aggregation import bin_samples
from repro.core.reducers import N_BUCKETS, QuantileSketch, bucket_of
from repro.core.sharding import ShardPlan
from repro.kernels import (binstats, binstats_ref, histbin, iqr_fences,
                           iqr_ref, rolling_ref, rolling_stats)


def _events(rng, n, total_ns):
    ts = rng.uniform(0, total_ns, n).astype(np.float32)
    vals = rng.normal(100, 30, n).astype(np.float32)
    return jnp.asarray(ts), jnp.asarray(vals)


@pytest.mark.parametrize("n,n_bins", [
    (100, 7), (1024, 128), (3000, 50), (4096, 256), (5, 3), (2048, 1),
])
def test_binstats_kernel_matches_ref(n, n_bins):
    rng = np.random.default_rng(n + n_bins)
    total = 1e9
    ts, vals = _events(rng, n, total)
    valid = jnp.asarray(rng.random(n) > 0.1)
    out_k = binstats(ts, vals, valid, total_ns=total, n_bins=n_bins,
                     use_kernel=True, interpret=True)
    out_r = binstats(ts, vals, valid, total_ns=total, n_bins=n_bins,
                     use_kernel=False, interpret=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("ev_tile,bin_tile", [(256, 128), (1024, 256)])
def test_binstats_tile_shapes(ev_tile, bin_tile):
    rng = np.random.default_rng(0)
    ts, vals = _events(rng, 2000, 1e9)
    valid = jnp.ones(2000, bool)
    out_k = binstats(ts, vals, valid, total_ns=1e9, n_bins=100,
                     use_kernel=True, ev_tile=ev_tile, bin_tile=bin_tile,
                     interpret=True)
    out_r = binstats(ts, vals, valid, total_ns=1e9, n_bins=100,
                     use_kernel=False, interpret=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-2)


def test_binstats_matches_host_aggregation():
    """Kernel contract == the numpy BinStats path used by the pipeline."""
    rng = np.random.default_rng(1)
    n, n_bins, total = 4000, 64, 1e9
    ts, vals = _events(rng, n, total)
    valid = jnp.ones(n, bool)
    out = np.asarray(binstats(ts, vals, valid, total_ns=total,
                              n_bins=n_bins, use_kernel=True, interpret=True))
    plan = ShardPlan(0, int(total), n_bins)
    # identical float32 binning contract
    bins = np.clip((np.asarray(ts) * np.float32(n_bins / total)
                    ).astype(np.int32), 0, n_bins - 1)
    ref = bin_samples(np.asarray(plan.boundaries()[bins], np.int64),
                      np.asarray(vals, np.float64), plan)
    np.testing.assert_allclose(out[:, 0], ref.count, atol=0)
    np.testing.assert_allclose(out[:, 1], ref.sum, rtol=1e-4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(1, 600), n_bins=st.integers(1, 64),
           seed=st.integers(0, 99))
    def test_binstats_property_sweep(n, n_bins, seed):
        rng = np.random.default_rng(seed)
        ts, vals = _events(rng, n, 1e8)
        valid = jnp.asarray(rng.random(n) > 0.2)
        k = binstats(ts, vals, valid, total_ns=1e8, n_bins=n_bins,
                     use_kernel=True, interpret=True)
        r = binstats(ts, vals, valid, total_ns=1e8, n_bins=n_bins,
                     use_kernel=False, interpret=True)
        np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                                   rtol=1e-5, atol=1e-2)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_binstats_property_sweep():
        pass


def test_binstats_multimetric_matches_single_runs():
    """A batched (M, N) pass returns, per metric, the same moments as M
    independent single-metric kernel calls (shared one-hot, one matmul)."""
    rng = np.random.default_rng(11)
    n, n_bins, total = 3000, 50, 1e9
    ts, v0 = _events(rng, n, total)
    v1 = jnp.asarray(rng.normal(5, 2, n).astype(np.float32))
    v2 = jnp.asarray(rng.uniform(0, 1e6, n).astype(np.float32))
    valid = jnp.asarray(rng.random(n) > 0.1)
    batch = jnp.stack([v0, v1, v2])
    mk = binstats(ts, batch, valid, total_ns=total, n_bins=n_bins,
                  use_kernel=True, interpret=True)
    mr = binstats(ts, batch, valid, total_ns=total, n_bins=n_bins,
                  use_kernel=False, interpret=True)
    assert mk.shape == (3, n_bins, 5)
    np.testing.assert_allclose(np.asarray(mk), np.asarray(mr),
                               rtol=1e-5, atol=1e-2)
    for j, v in enumerate((v0, v1, v2)):
        single = binstats(ts, v, valid, total_ns=total, n_bins=n_bins,
                          use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(mk[j]), np.asarray(single),
                                   rtol=1e-5, atol=1e-2)
        # counts are metric-independent and exactly shared
        np.testing.assert_array_equal(np.asarray(mk[j][:, 0]),
                                      np.asarray(mk[0][:, 0]))


# --- histbin ---------------------------------------------------------------------

@pytest.mark.parametrize("n,n_bins", [
    (100, 7), (1024, 128), (3000, 50), (5, 3), (2048, 1),
])
def test_histbin_kernel_matches_ref(n, n_bins):
    """Pallas double-one-hot scatter-as-matmul ≡ segment_sum oracle,
    EXACTLY (both count integer events in float32)."""
    rng = np.random.default_rng(n + n_bins)
    total = 1e9
    ts, _ = _events(rng, n, total)
    vals = jnp.asarray(np.abs(rng.normal(5000, 3000, n)), jnp.float32)
    valid = jnp.asarray(rng.random(n) > 0.1)
    out_k = histbin(ts, vals, valid, total_ns=total, n_bins=n_bins,
                    use_kernel=True, interpret=True)
    out_r = histbin(ts, vals, valid, total_ns=total, n_bins=n_bins,
                    use_kernel=False, interpret=True)
    assert out_k.shape == (n_bins, N_BUCKETS)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    assert float(np.asarray(out_k).sum()) == float(np.asarray(valid).sum())


def test_histbin_multimetric_matches_single_runs():
    """A batched (M, N) pass returns, per metric, the same histogram as M
    independent single-metric kernel calls (shared bin one-hot)."""
    rng = np.random.default_rng(5)
    n, n_bins, total = 2000, 40, 1e9
    ts, _ = _events(rng, n, total)
    v0 = jnp.asarray(np.abs(rng.normal(1e4, 3e3, n)), jnp.float32)
    v1 = jnp.asarray(rng.uniform(1, 1e7, n).astype(np.float32))
    valid = jnp.asarray(rng.random(n) > 0.2)
    batch = jnp.stack([v0, v1])
    mk = histbin(ts, batch, valid, total_ns=total, n_bins=n_bins,
                 use_kernel=True, interpret=True)
    assert mk.shape == (2, n_bins, N_BUCKETS)
    for j, v in enumerate((v0, v1)):
        single = histbin(ts, v, valid, total_ns=total, n_bins=n_bins,
                         use_kernel=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(mk[j]),
                                      np.asarray(single))


def test_histbin_feeds_quantile_sketch():
    """Kernel output drops into QuantileSketch and answers quantiles that
    match the host float64 sketch path on boundary-safe values."""
    rng = np.random.default_rng(9)
    n, n_bins, total = 4000, 16, 1e9
    ts = rng.uniform(0, total, n).astype(np.float32)
    vals = np.abs(rng.lognormal(8.0, 1.0, n)).astype(np.float32)
    valid = np.ones(n, bool)
    out = np.asarray(histbin(jnp.asarray(ts), jnp.asarray(vals),
                             jnp.asarray(valid), total_ns=total,
                             n_bins=n_bins, use_kernel=True, interpret=True))
    sk = QuantileSketch(counts=out.astype(np.float64))
    # host sketch over identical float32-binned rows
    host = np.zeros((n_bins, N_BUCKETS))
    bins = np.clip((ts * np.float32(n_bins / total)).astype(np.int32),
                   0, n_bins - 1)
    np.add.at(host, (bins, bucket_of(vals.astype(np.float64))), 1.0)
    hs = QuantileSketch(counts=host)
    occ = sk.total() > 0
    for q in (0.5, 0.95, 0.99):
        np.testing.assert_allclose(sk.quantile(q)[occ],
                                   hs.quantile(q)[occ], rtol=1e-6)


# --- iqr ------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 100, 255, 1024])
def test_iqr_kernel_matches_ref(n):
    rng = np.random.default_rng(n)
    s = rng.normal(10, 2, n).astype(np.float32)
    s[rng.integers(0, n, 3)] *= 10
    occ = s != 0
    k = iqr_fences(jnp.asarray(s), jnp.asarray(occ), use_kernel=True,
                   interpret=True)
    r = iqr_fences(jnp.asarray(s), jnp.asarray(occ), use_kernel=False,
                   interpret=True)
    for key in ("q1", "q3", "hi_fence"):
        np.testing.assert_allclose(float(k[key]), float(r[key]),
                                   rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(k["flags"]),
                                  np.asarray(r["flags"]))


def test_iqr_kernel_sorted_output_is_sorted():
    rng = np.random.default_rng(0)
    s = rng.normal(0, 5, 200).astype(np.float32)
    k = iqr_fences(jnp.asarray(s), jnp.asarray(np.ones(200, bool)),
                   use_kernel=True, interpret=True)
    srt = np.asarray(k["sorted"])
    assert np.all(np.diff(srt) >= 0)


def test_iqr_matches_numpy_quartiles():
    rng = np.random.default_rng(5)
    s = np.abs(rng.normal(10, 3, 501)).astype(np.float32)
    k = iqr_fences(jnp.asarray(s), jnp.asarray(s != 0), use_kernel=True,
                   interpret=True)
    q1, q3 = np.percentile(s, [25, 75])
    np.testing.assert_allclose(float(k["q1"]), q1, rtol=2e-2)
    np.testing.assert_allclose(float(k["q3"]), q3, rtol=2e-2)


# --- rolling ---------------------------------------------------------------------

@pytest.mark.parametrize("n,window", [(64, 8), (500, 32), (1000, 100),
                                      (100, 1)])
def test_rolling_kernel_matches_ref(n, window):
    rng = np.random.default_rng(n + window)
    x = rng.normal(0, 2, n).astype(np.float32)
    k = rolling_stats(jnp.asarray(x), window=window, use_kernel=True,
                      interpret=True)
    r = rolling_stats(jnp.asarray(x), window=window, use_kernel=False,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                               rtol=1e-4, atol=1e-4)


def test_rolling_matches_numpy():
    rng = np.random.default_rng(2)
    n, w = 300, 16
    x = rng.normal(5, 3, n).astype(np.float32)
    out = np.asarray(rolling_stats(jnp.asarray(x), window=w,
                                   use_kernel=True, interpret=True))
    for i in (w - 1, n // 2, n - 1):
        seg = x[max(0, i - w + 1): i + 1]
        np.testing.assert_allclose(out[i, 0], seg.mean(), rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(out[i, 1], seg.std(), rtol=1e-3,
                                   atol=1e-3)


# --- ssd (fused SSD chunk scan) ----------------------------------------------------

@pytest.mark.parametrize("b,s,H,P,G,N,chunk", [
    (2, 37, 4, 8, 2, 16, 8),
    (1, 64, 2, 16, 1, 32, 16),
    (2, 16, 8, 8, 8, 8, 16),     # s < padded multiple, G == H
])
def test_ssd_kernel_matches_oracle_and_scan(b, s, H, P, G, N, chunk):
    from repro.kernels.ssd import ssd_fused
    from repro.models.ssm import ssd_scan
    rng = np.random.default_rng(b + s + H)
    xs = jnp.asarray(rng.normal(size=(b, s, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (b, s, H)), jnp.float32)
    A_log = jnp.asarray(rng.uniform(-1, 1, (H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, G, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, G, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    yk, hk = ssd_fused(xs, dt, A_log, B, C, D, chunk=chunk,
                       use_kernel=True, interpret=True)
    yr, hr = ssd_fused(xs, dt, A_log, B, C, D, chunk=chunk,
                       use_kernel=False, interpret=True)
    y0, h0 = ssd_scan(xs, dt, A_log, B, C, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y0),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(h0),
                               rtol=1e-4, atol=1e-4)


def test_ssd_kernel_bf16_inputs():
    from repro.kernels.ssd import ssd_fused
    rng = np.random.default_rng(0)
    b, s, H, P, G, N = 1, 32, 2, 8, 1, 16
    xs = jnp.asarray(rng.normal(size=(b, s, H, P)), jnp.bfloat16)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (b, s, H)), jnp.float32)
    A_log = jnp.asarray(rng.uniform(-1, 1, (H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, G, N)), jnp.bfloat16)
    C = jnp.asarray(rng.normal(size=(b, s, G, N)), jnp.bfloat16)
    D = jnp.ones((H,), jnp.float32)
    yk, hk = ssd_fused(xs, dt, A_log, B, C, D, chunk=16, use_kernel=True,
                       interpret=True)
    yr, hr = ssd_fused(xs, dt, A_log, B, C, D, chunk=16, use_kernel=False,
                       interpret=True)
    assert yk.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_ssm_block_pallas_path_matches_xla():
    import dataclasses as dc
    from repro.models.ssm import SSMConfig, ssm_init, ssm_forward
    rng = np.random.default_rng(0)
    cfg = SSMConfig(d_model=32, d_state=16, head_dim=8, n_groups=2,
                    chunk=8)
    params = ssm_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.normal(size=(2, 20, 32)), jnp.float32)
    out_x, cache_x = ssm_forward(params, x, cfg)
    cfg_p = dc.replace(cfg, use_pallas=True)
    out_p, cache_p = ssm_forward(params, x, cfg_p)
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_p),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(cache_x["state"]),
                               np.asarray(cache_p["state"]),
                               rtol=1e-4, atol=1e-4)


# --- flashattn ---------------------------------------------------------------------

@pytest.mark.parametrize("s,causal,window,dtype", [
    (100, True, 0, jnp.float32),
    (64, True, 16, jnp.float32),
    (80, False, 0, jnp.float32),
    (96, True, 0, jnp.bfloat16),
])
def test_flash_attention_kernel_matches_refs(s, causal, window, dtype):
    from repro.kernels.flashattn import flash_attention
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(s)
    b, h, hd = 2, 3, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    ok = flash_attention(q, k, v, causal=causal, window=window,
                         q_tile=32, kv_tile=32, use_kernel=True,
                         interpret=True)
    orf = flash_attention(q, k, v, causal=causal, window=window,
                          use_kernel=False, interpret=True)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(ok, np.float32),
                               np.asarray(orf, np.float32),
                               rtol=tol, atol=tol)
    if dtype == jnp.float32:
        oc = chunked_attention(q, k, v, causal=causal, window=window,
                               q_chunk=32, kv_chunk=32)
        np.testing.assert_allclose(np.asarray(ok), np.asarray(oc),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_tile_invariance():
    from repro.kernels.flashattn import flash_attention
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
    a = flash_attention(q, k, v, q_tile=16, kv_tile=16, interpret=True)
    b = flash_attention(q, k, v, q_tile=64, kv_tile=32, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)
