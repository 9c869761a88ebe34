"""Mamba2 SSD mixer (state-space duality, arXiv:2405.21060) + decode state.

Train/prefill runs the **chunked SSD algorithm** as a single `lax.scan` over
sequence chunks carrying the (B, H, P, N) inter-chunk state:

  intra-chunk:  Y_d = (C Bᵀ ⊙ L) X̄          (quadratic within the chunk —
                                              this is the "duality": a masked
                                              attention-like matmul the MXU
                                              eats directly)
  inter-chunk:  h_c = exp(ΣdtA) h_{c-1} + Σ_j exp(cum_q - cum_j) B_j ⊗ x̄_j
                Y_o = exp(cum) · C h_{c-1}

All exponent arguments are ≤ 0 by construction (dtA < 0), so the scan is
overflow-free at any context length — what lets ``long_500k`` run.

Decode is the O(1) recurrence  h ← a·h + dt·x⊗B,  y = C·h + D·x  plus a
rolling window for the causal depthwise conv.

Projections are SEPARATE parameters per component (z/x/B/C/dt) instead of
one fused in_proj so tensor-parallel sharding can split x/z/dt over heads
while B/C (group-shared, tiny) replicate — see shardrules.py.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import dense_init


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_model: int
    d_state: int = 128          # N
    head_dim: int = 64          # P
    expand: int = 2
    n_groups: int = 1           # G
    conv_width: int = 4
    chunk: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1
    # §Perf: route the chunk scan through the fused Pallas SSD kernel
    # (kernels/ssd) instead of the XLA chunked formulation
    use_pallas: bool = False

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim


# --- init ---------------------------------------------------------------------

def ssm_init(key, cfg: SSMConfig) -> Dict:
    ks = jax.random.split(key, 10)
    d, di, gn, h, w = (cfg.d_model, cfg.d_inner,
                       cfg.n_groups * cfg.d_state, cfg.n_heads,
                       cfg.conv_width)
    # dt bias initialised so softplus(dt_bias) spans [dt_min, dt_max]
    u = jax.random.uniform(ks[0], (h,))
    dt = jnp.exp(u * (np.log(cfg.dt_max) - np.log(cfg.dt_min))
                 + np.log(cfg.dt_min))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus
    return {
        "in_z": dense_init(ks[1], (d, di)),
        "in_x": dense_init(ks[2], (d, di)),
        "in_b": dense_init(ks[3], (d, gn)),
        "in_c": dense_init(ks[4], (d, gn)),
        "in_dt": dense_init(ks[5], (d, h)),
        "conv_x": {"w": dense_init(ks[6], (w, di), fan_in=w),
                   "b": jnp.zeros((di,), jnp.float32)},
        "conv_b": {"w": dense_init(ks[7], (w, gn), fan_in=w),
                   "b": jnp.zeros((gn,), jnp.float32)},
        "conv_c": {"w": dense_init(ks[8], (w, gn), fan_in=w),
                   "b": jnp.zeros((gn,), jnp.float32)},
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "ssm_norm": {"scale": jnp.ones((di,), jnp.float32)},
        "out_proj": dense_init(ks[9], (di, d), fan_in=di),
    }


# --- causal depthwise conv ------------------------------------------------------

def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 ) -> jnp.ndarray:
    """x: (B, S, C); w: (width, C) depthwise; left-padded causal + silu."""
    width, c = w.shape
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype), window_strides=(1,),
        padding="VALID", dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=c)
    return jax.nn.silu(out + b.astype(x.dtype))


def _conv_step(state: jnp.ndarray, x_new: jnp.ndarray, w: jnp.ndarray,
               b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decode: state (B, width-1, C), x_new (B, 1, C) -> (out, new_state)."""
    window = jnp.concatenate([state, x_new.astype(state.dtype)], axis=1)
    out = jnp.einsum("bwc,wc->bc", window.astype(x_new.dtype),
                     w.astype(x_new.dtype)) + b.astype(x_new.dtype)
    return jax.nn.silu(out)[:, None, :], window[:, 1:, :]


# --- chunked SSD scan ------------------------------------------------------------

def ssd_scan(xs: jnp.ndarray, dt: jnp.ndarray, A_log: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, D: jnp.ndarray,
             chunk: int, h_init: Optional[jnp.ndarray] = None,
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD.

    xs: (b, s, H, P)   dt: (b, s, H) — already masked to 0 on padding
    B, C: (b, s, G, N) A_log, D: (H,)
    Returns y (b, s, H, P) fp-of-xs, final state (b, H, P, N) fp32.
    """
    b, s, H, Pd = xs.shape
    G = B.shape[2]
    hg = H // G
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 ⇒ identity step
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))

    A = -jnp.exp(A_log.astype(jnp.float32))            # (H,) < 0

    # chunk-major layout for the scan: (nc, b, q, ...)
    def chunkify(x):
        return jnp.moveaxis(
            x.reshape((b, nc, q) + x.shape[2:]), 1, 0)

    xs_c, dt_c = chunkify(xs), chunkify(dt)
    B_c, C_c = chunkify(B), chunkify(C)

    # remat per chunk: the (q × q) decay matrix L is recomputed in the
    # backward pass (same rationale as the flash-attention inner remat)
    @jax.checkpoint
    def body(h_prev, inp):
        xck, dtk, Bk, Ck = inp                          # (b,q,H,P) etc.
        hp = h_prev.reshape(b, G, hg, Pd, -1)           # grouped state view
        dtf = dtk.astype(jnp.float32)
        dtA = dtf * A                                   # (b,q,H) ≤ 0
        cum = jnp.cumsum(dtA, axis=1)                   # (b,q,H)
        last = cum[:, -1, :]                            # (b,H)

        xbar = (dtf[..., None] * xck.astype(jnp.float32))   # (b,q,H,P)
        xg = xbar.reshape(b, q, G, hg, Pd)
        cumg = cum.reshape(b, q, G, hg)

        # intra-chunk: (C Bᵀ ⊙ L) X̄ — the duality matmul
        scores = jnp.einsum("bign,bjgn->bgij",
                            Ck.astype(jnp.float32), Bk.astype(jnp.float32))
        li = cumg[:, :, :, :, None] - cumg.transpose(0, 2, 3, 1)[:, None]
        # li: (b,i,g,h,j); mask j<=i
        iota_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        iota_j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        causal = (iota_j <= iota_i)[None, :, None, None, :]
        # Mask the exponent BEFORE exp: in the non-causal region li > 0
        # grows with trained dt, exp overflows to +inf, and the outer
        # where's backward then computes 0·inf = NaN (the hymba hybrid
        # block trains dt large enough to hit this by ~step 12).
        li = jnp.where(causal, li, 0.0)
        L = jnp.where(causal, jnp.exp(li), 0.0)         # (b,i,g,h,j)
        y_intra = jnp.einsum("bgij,bighj,bjghp->bighp",
                             scores, L, xg)

        # inter-chunk: contribution of the carried state
        y_inter = jnp.einsum("bign,bghpn,bigh->bighp",
                             Ck.astype(jnp.float32), hp,
                             jnp.exp(cumg))

        # state update for the next chunk
        decay = jnp.exp(last.reshape(b, 1, G, hg) - cumg)   # (b,j,g,h)
        S = jnp.einsum("bjgn,bjghp,bjgh->bghpn", Bk.astype(jnp.float32),
                       xg, decay)
        h_new = (jnp.exp(last).reshape(b, G, hg, 1, 1) * hp + S
                 ).reshape(b, H, Pd, -1)

        y = (y_intra + y_inter).reshape(b, q, H, Pd)
        y = y + D.astype(jnp.float32)[None, None, :, None] * \
            xck.astype(jnp.float32)
        return h_new, y.astype(xs.dtype)

    h0 = (h_init if h_init is not None
          else jnp.zeros((b, G, hg, Pd, B.shape[-1]), jnp.float32)
          .reshape(b, H, Pd, -1))
    h_fin, ys = jax.lax.scan(body, h0, (xs_c, dt_c, B_c, C_c))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * q, H, Pd)[:, :s]
    return y, h_fin


# --- block forward / decode -------------------------------------------------------

def _gated_norm(scale, y, z, eps=1e-6):
    g = y * jax.nn.silu(z)
    gf = g.astype(jnp.float32)
    var = jnp.mean(gf * gf, axis=-1, keepdims=True)
    return (gf * jax.lax.rsqrt(var + eps) * scale).astype(y.dtype)


def _constrain_ssm(t, ctx, head_axis: Optional[int]):
    """§Perf H7: pin the SSD head axis to the tensor axis (and batch to
    the batch axes) — without the anchor GSPMD re-gathers the group-shared
    B/C tensors inside every chunk iteration (0.5 MB × 19k on mamba2)."""
    if ctx is None or ctx.tensor is None:
        return t
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = [None] * t.ndim
    if ctx.batch and t.shape[0] % ctx.batch_size == 0:
        spec[0] = ctx.batch
    if head_axis is not None and \
            t.shape[head_axis] % ctx.tensor_size == 0:
        spec[head_axis] = ctx.tensor
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(ctx.mesh, P(*spec)))


def ssm_forward(params, x, cfg: SSMConfig, ctx=None,
                ) -> Tuple[jnp.ndarray, Dict]:
    """Train/prefill. x: (B, S, D). Returns (out, decode cache entries)."""
    bsz, s, _ = x.shape
    dt_ = x.dtype
    z = jnp.einsum("bsd,de->bse", x, params["in_z"].astype(dt_))
    xr = jnp.einsum("bsd,de->bse", x, params["in_x"].astype(dt_))
    Br = jnp.einsum("bsd,de->bse", x, params["in_b"].astype(dt_))
    Cr = jnp.einsum("bsd,de->bse", x, params["in_c"].astype(dt_))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, params["in_dt"].astype(dt_))

    w = cfg.conv_width
    xc = _causal_conv(xr, params["conv_x"]["w"], params["conv_x"]["b"])
    Bc = _causal_conv(Br, params["conv_b"]["w"], params["conv_b"]["b"])
    Cc = _causal_conv(Cr, params["conv_c"]["w"], params["conv_c"]["b"])

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"])            # (B,S,H)
    xs = xc.reshape(bsz, s, cfg.n_heads, cfg.head_dim)
    B3 = Bc.reshape(bsz, s, cfg.n_groups, cfg.d_state)
    C3 = Cc.reshape(bsz, s, cfg.n_groups, cfg.d_state)
    xs = _constrain_ssm(xs, ctx, head_axis=2)
    dt = _constrain_ssm(dt, ctx, head_axis=2)
    B3 = _constrain_ssm(B3, ctx, head_axis=None)   # group-shared: replicate
    C3 = _constrain_ssm(C3, ctx, head_axis=None)

    if cfg.use_pallas:
        from repro.kernels.ssd import ssd_fused
        y, h_fin = ssd_fused(xs, dt, params["A_log"], B3, C3,
                             params["D"], chunk=cfg.chunk,
                             interpret=jax.default_backend() != "tpu")
    else:
        y, h_fin = ssd_scan(xs, dt, params["A_log"], B3, C3,
                            params["D"], cfg.chunk)
    y = y.reshape(bsz, s, cfg.d_inner)
    y = _gated_norm(params["ssm_norm"]["scale"], y, z)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(dt_))

    # decode cache: conv tails (pre-conv inputs) + final SSM state
    def tail(u):
        t = u[:, -(w - 1):, :]
        need = (w - 1) - t.shape[1]
        if need > 0:
            t = jnp.pad(t, ((0, 0), (need, 0), (0, 0)))
        return t
    cache = {"conv_x": tail(xr), "conv_b": tail(Br), "conv_c": tail(Cr),
             "state": h_fin}
    return out, cache


def ssm_decode(params, x, cache, cfg: SSMConfig,
               ) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: (B, 1, D); cache from ssm_forward/init."""
    bsz = x.shape[0]
    dt_ = x.dtype
    z = jnp.einsum("bsd,de->bse", x, params["in_z"].astype(dt_))
    xr = jnp.einsum("bsd,de->bse", x, params["in_x"].astype(dt_))
    Br = jnp.einsum("bsd,de->bse", x, params["in_b"].astype(dt_))
    Cr = jnp.einsum("bsd,de->bse", x, params["in_c"].astype(dt_))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, params["in_dt"].astype(dt_))

    xc, st_x = _conv_step(cache["conv_x"], xr,
                          params["conv_x"]["w"], params["conv_x"]["b"])
    Bc, st_b = _conv_step(cache["conv_b"], Br,
                          params["conv_b"]["w"], params["conv_b"]["b"])
    Cc, st_c = _conv_step(cache["conv_c"], Cr,
                          params["conv_c"]["w"], params["conv_c"]["b"])

    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32)
                         + params["dt_bias"])            # (B,H)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    a = jnp.exp(dt * A)                                  # (B,H)

    H, Pd, G, N = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    hg = H // G
    xh = (dt[..., None] *
          xc[:, 0].astype(jnp.float32).reshape(bsz, H, Pd))   # x̄ (B,H,P)
    B1 = Bc[:, 0].astype(jnp.float32).reshape(bsz, G, N)
    C1 = Cc[:, 0].astype(jnp.float32).reshape(bsz, G, N)

    Bh = jnp.repeat(B1, hg, axis=1)                      # (B,H,N)
    Ch = jnp.repeat(C1, hg, axis=1)
    h_new = a[..., None, None] * cache["state"] + \
        xh[..., None] * Bh[:, :, None, :]                # (B,H,P,N)
    y = jnp.einsum("bhpn,bhn->bhp", h_new, Ch)
    y = y + params["D"][None, :, None] * \
        xc[:, 0].astype(jnp.float32).reshape(bsz, H, Pd)
    y = y.reshape(bsz, 1, cfg.d_inner).astype(dt_)
    y = _gated_norm(params["ssm_norm"]["scale"], y, z)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(dt_))
    return out, {"conv_x": st_x, "conv_b": st_b, "conv_c": st_c,
                 "state": h_new}


def ssm_init_cache(cfg: SSMConfig, batch: int, dtype=jnp.bfloat16) -> Dict:
    w, di, gn = cfg.conv_width, cfg.d_inner, cfg.n_groups * cfg.d_state
    return {
        "conv_x": jnp.zeros((batch, w - 1, di), dtype),
        "conv_b": jnp.zeros((batch, w - 1, gn), dtype),
        "conv_c": jnp.zeros((batch, w - 1, gn), dtype),
        "state": jnp.zeros((batch, cfg.n_heads, cfg.head_dim, cfg.d_state),
                           jnp.float32),
    }
