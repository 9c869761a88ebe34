"""Explicit tensor-parallel Megatron blocks (§Perf H2).

The GSPMD baseline emits the TP activation all-reduces wherever the
partitioner places them — measured on nemotron-4-15b train_4k: 4 fp32
(B,S,D) all-reduces per layer-microbatch (fwd o-proj, fwd ffn-down, and
two backward cotangent reductions, re-run under remat), 386 GB wire on a
594 GB total. These shard_map blocks pin the schedule to the theoretical
minimum — ONE bf16 psum forward and ONE bf16 psum backward per block, by
construction:

  * forward: every matmul is local to the tensor rank (q/o heads and ffn
    hidden are axis-sharded); the single partial-sum output is cast to the
    activation dtype BEFORE ``lax.psum`` — the wire moves bf16, not the
    fp32 the CPU-backend dot promotion would hand GSPMD;
  * backward (via shard_map AD): the replicated-input cotangent psum is
    the transpose of the broadcast — also bf16, also one per block.

Applicability: heads (attention) / d_ff (FFN) divisible by the tensor
axis; non-divisible archs (hymba 25H, qwen2-vl 28H) keep the GSPMD path —
recorded per-arch in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from .attention import AttnConfig, chunked_attention
from .layers import ACTIVATIONS, apply_rope
from .shardrules import ParallelCtx


def _bspec(ctx: ParallelCtx, b: int, ndim: int) -> P:
    if ctx.batch and b % ctx.batch_size == 0:
        return P(ctx.batch, *([None] * (ndim - 1)))
    return P(*([None] * ndim))


def ffn_tp_applicable(d_ff: int, ctx: Optional[ParallelCtx]) -> bool:
    return (ctx is not None and ctx.explicit_tp
            and ctx.tensor is not None
            and ctx.tensor_size > 1 and d_ff % ctx.tensor_size == 0)


def ffn_tp(params: Dict, x: jnp.ndarray, activation: str,
           ctx: ParallelCtx) -> jnp.ndarray:
    """Column×row-parallel FFN with one explicit bf16 psum."""
    ax = ctx.tensor
    act = ACTIVATIONS[activation]
    gated = "w_gate" in params

    def body(p, xl):
        dt = xl.dtype
        up = jnp.einsum("bsd,df->bsf", xl, p["w_up"].astype(dt))
        if gated:
            g = jnp.einsum("bsd,df->bsf", xl, p["w_gate"].astype(dt))
            h = act(g) * up
        else:
            h = act(up)
        part = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
        return jax.lax.psum(part.astype(dt), ax)

    pspec = {"w_up": P(None, ax), "w_down": P(ax, None)}
    if gated:
        pspec["w_gate"] = P(None, ax)
    bs = _bspec(ctx, x.shape[0], 3)
    fn = jax.shard_map(body, mesh=ctx.mesh, in_specs=(pspec, bs),
                       out_specs=bs, check_vma=False)
    return fn({k: params[k] for k in pspec}, x)


def attn_tp_applicable(cfg: AttnConfig, ctx: Optional[ParallelCtx],
                       mode: str) -> bool:
    return (ctx is not None and ctx.explicit_tp
            and ctx.tensor is not None
            and ctx.tensor_size > 1 and not cfg.is_mla
            and mode in ("train", "prefill")
            and cfg.n_heads % ctx.tensor_size == 0
            and cfg.rope != "mrope")


def attn_tp(params: Dict, x: jnp.ndarray, cfg: AttnConfig, positions,
            ctx: ParallelCtx, mode: str,
            ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Head-parallel attention block with one explicit bf16 psum.

    Query heads shard over the tensor axis; the (small, non-divisible)
    KV projections replicate and each rank statically expands ITS head
    slice. Returns (y, {"k","v"} compact GQA cache for prefill)."""
    ax = ctx.tensor
    tp = ctx.tensor_size
    h_loc = cfg.n_heads // tp
    g = cfg.n_heads // cfg.n_kv_heads

    def body(p, xl, pos):
        dt = xl.dtype
        q = jnp.einsum("bsd,dhk->bshk", xl, p["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", xl, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", xl, p["wv"].astype(dt))
        if "bq" in p:
            q = q + p["bq"].astype(dt)
            k = k + p["bk"].astype(dt)
            v = v + p["bv"].astype(dt)
        if cfg.rope in ("rope", "partial"):
            frac = cfg.rotary_fraction if cfg.rope == "partial" else 1.0
            q = apply_rope(q, pos, cfg.rope_theta, frac)
            k = apply_rope(k, pos, cfg.rope_theta, frac)
        # expand MY query-head slice from the replicated KV heads
        i = jax.lax.axis_index(ax)
        my_map = (i * h_loc + jnp.arange(h_loc)) // g
        k_x = jnp.take(k, my_map, axis=2)
        v_x = jnp.take(v, my_map, axis=2)
        out = chunked_attention(
            q, k_x, v_x, causal=cfg.causal, window=cfg.window,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        part = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
        y = jax.lax.psum(part.astype(dt), ax)
        return y, k, v

    pspec = {"wq": P(None, ax, None), "wk": P(), "wv": P(),
             "wo": P(ax, None, None)}
    in_p = {k: params[k] for k in ("wq", "wk", "wv", "wo")}
    if "bq" in params:
        pspec.update({"bq": P(ax, None), "bk": P(), "bv": P()})
        in_p.update({k: params[k] for k in ("bq", "bk", "bv")})
    bs3 = _bspec(ctx, x.shape[0], 3)
    bs4 = _bspec(ctx, x.shape[0], 4)
    fn = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(pspec, bs3, P()),
        out_specs=(bs3, bs4, bs4), check_vma=False)
    y, k, v = fn(in_p, x, positions)
    return y, ({"k": k, "v": v} if mode == "prefill" else None)
