"""Mixture-of-Experts layer: sort-based dispatch + expert-parallel all_to_all.

Design (DESIGN.md §4): NO one-hot dispatch einsum — a (T, E·C) one-hot
matmul would dominate compiled HLO FLOPs by 100–10000× and wreck the
roofline's useful-FLOPs ratio. Instead:

  1. route: top-k over router softmax (fp32),
  2. sort token-expert assignments by expert id (argsort — XLA sort HLO),
  3. capacity-bounded scatter into an (E, C, D) buffer (overflow drops,
     counted and exported in the metrics),
  4. dense per-expert GEMMs (the MXU-friendly part),
  5. gather-combine back through the same permutation.

Three execution paths, one math:
  * ``local``      — no mesh (unit tests / smoke configs),
  * ``ep``         — shard_map: tokens sequence-sharded over the tensor
    axis, experts sharded over the tensor axis, two ``all_to_all``s move
    (E, C_loc, D) buffers over ICI (DeepSpeed-MoE pattern),
  * ``replicated`` — decode (S=1 cannot shard): every tensor-rank routes
    the same tokens, computes ITS expert slice, and a ``psum`` combines.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from .layers import dense_init
from .shardrules import ParallelCtx
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                       # per-expert hidden
    n_experts: int
    top_k: int
    n_shared: int = 0               # shared (always-on) experts, fused
    capacity_factor: float = 1.25
    renorm_weights: bool = True     # deepseek renormalizes top-k probs
    router_aux_weight: float = 0.01


def moe_init(key, cfg: MoEConfig) -> Dict:
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": dense_init(ks[0], (d, e)),
        "experts": {
            "w_up": dense_init(ks[1], (e, d, f)),
            "w_gate": dense_init(ks[2], (e, d, f)),
            "w_down": dense_init(ks[3], (e, f, d), fan_in=f),
        },
    }
    if cfg.n_shared > 0:
        fs = cfg.n_shared * f
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {"w_up": dense_init(ks2[0], (d, fs)),
                       "w_gate": dense_init(ks2[1], (d, fs)),
                       "w_down": dense_init(ks2[2], (fs, d), fan_in=fs)}
    return p


def _route(router_w, tokens, cfg: MoEConfig):
    """tokens (T, D) -> (top_w (T,k) f32, top_i (T,k) i32, aux_loss)."""
    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.renorm_weights:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing loss: E * <f_e, p_e>
    e = cfg.n_experts
    assign = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f_e = assign / jnp.maximum(assign.sum(), 1.0)
    p_e = probs.mean(0)
    aux = e * jnp.sum(f_e * p_e)
    return top_w, top_i, aux


def _dispatch(tokens, top_i, cfg: MoEConfig, capacity: int):
    """Sort-based scatter into the (E*C, D) buffer.

    Returns (buf (E, C, D), slot (T*k,), order (T*k,), keep (T*k,))."""
    t, d = tokens.shape
    k, e = cfg.top_k, cfg.n_experts
    flat_e = top_i.reshape(-1)                          # (T*k,)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(t * k, dtype=jnp.int32) - seg_start
    slot = sorted_e * capacity + pos
    keep = pos < capacity
    src = order // k                                    # token per assignment
    buf = jnp.zeros((e * capacity, d), tokens.dtype)
    buf = buf.at[jnp.where(keep, slot, e * capacity)].set(
        tokens[src], mode="drop")
    return buf.reshape(e, capacity, d), slot, order, keep


def _expert_ffn(experts, buf):
    """(E, C, D) x (E, D, F) -> (E, C, D) gated-silu expert GEMMs."""
    dt = buf.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, experts["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, experts["w_up"].astype(dt))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, experts["w_down"].astype(dt))


def _combine(out_buf, slot, order, keep, top_w, t: int, d: int, k: int):
    """Gather expert outputs back and weight-sum over the k assignments."""
    flat = out_buf.reshape(-1, d)
    e_cap = flat.shape[0]
    safe = jnp.where(keep, slot, 0)
    contrib = flat[safe] * (top_w.reshape(-1)[order]
                            * keep.astype(jnp.float32))[:, None].astype(
                                flat.dtype)
    out = jnp.zeros((t, d), flat.dtype)
    return out.at[order // k].add(contrib)


def _capacity(tokens_per_shard: int, cfg: MoEConfig) -> int:
    c = int(np.ceil(tokens_per_shard * cfg.top_k / cfg.n_experts
                    * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)         # pad to a lane-friendly multiple


# --- the three execution paths -------------------------------------------------

def _moe_local(params, tokens, cfg: MoEConfig):
    t, d = tokens.shape
    top_w, top_i, aux = _route(params["router"], tokens, cfg)
    cap = _capacity(t, cfg)
    buf, slot, order, keep = _dispatch(tokens, top_i, cfg, cap)
    out_buf = _expert_ffn(params["experts"], buf)
    out = _combine(out_buf, slot, order, keep, top_w, t, d, cfg.top_k)
    dropped = 1.0 - keep.astype(jnp.float32).mean()
    return out, aux, dropped


def _moe_ep_body(params, tokens, cfg: MoEConfig, tensor_axis: str,
                 tp: int):
    """shard_map body: tokens (T_loc, D) local; experts (E_loc, ...) local."""
    t, d = tokens.shape
    e, k = cfg.n_experts, cfg.top_k
    top_w, top_i, aux = _route(params["router"], tokens, cfg)
    cap = _capacity(t, cfg)
    buf, slot, order, keep = _dispatch(tokens, top_i, cfg, cap)
    # (E, C, D) -> split E over ranks -> recv (E_loc, tp*C, D)
    buf = jax.lax.all_to_all(buf, tensor_axis, split_axis=0, concat_axis=1,
                             tiled=True)
    out_buf = _expert_ffn(params["experts"], buf)
    # route results back: (E_loc, tp*C, D) -> (E, C, D)
    out_buf = jax.lax.all_to_all(out_buf, tensor_axis, split_axis=1,
                                 concat_axis=0, tiled=True)
    out = _combine(out_buf, slot, order, keep, top_w, t, d, k)
    dropped = 1.0 - keep.astype(jnp.float32).mean()
    return out, jax.lax.pmean(aux, tensor_axis), \
        jax.lax.pmean(dropped, tensor_axis)


def _moe_stationary_body(params, tokens, cfg: MoEConfig, all_axes,
                         tensor_axis: str, tp: int):
    """§Perf H8 decode path: weights stay put, tokens replicate.

    tokens (T, D) replicated over EVERY mesh axis (decode batches are
    KB-sized; the expert tables are GBs). Each device holds its
    (E/tp, D, F/fsdp) weight shard, computes partials for all tokens, and
    one token-sized psum over the whole mesh combines — replacing the
    52 GB/step expert-weight gathers measured on deepseek decode_32k."""
    t, d = tokens.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // tp
    top_w, top_i, aux = _route(params["router"], tokens, cfg)
    cap = _capacity(t, cfg)
    buf, slot, order, keep = _dispatch(tokens, top_i, cfg, cap)
    r = jax.lax.axis_index(tensor_axis)
    my = jax.lax.dynamic_slice_in_dim(buf, r * e_loc, e_loc, axis=0)
    out_loc = _expert_ffn(params["experts"], my)   # F-shard partials
    out_buf = jnp.zeros((e, cap, d), out_loc.dtype)
    out_buf = jax.lax.dynamic_update_slice_in_dim(out_buf, out_loc,
                                                  r * e_loc, axis=0)
    out = _combine(out_buf, slot, order, keep, top_w, t, d, k)
    for ax in all_axes:
        out = jax.lax.psum(out, ax)
    dropped = 1.0 - keep.astype(jnp.float32).mean()
    return out, aux, dropped


def _moe_replicated_body(params, tokens, cfg: MoEConfig, tensor_axis: str,
                         tp: int):
    """Decode path: identical dispatch on every tensor rank, local expert
    slice, psum combine. tokens (T, D) replicated over the tensor axis."""
    t, d = tokens.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // tp
    top_w, top_i, aux = _route(params["router"], tokens, cfg)
    cap = _capacity(t, cfg)
    buf, slot, order, keep = _dispatch(tokens, top_i, cfg, cap)
    r = jax.lax.axis_index(tensor_axis)
    my = jax.lax.dynamic_slice_in_dim(buf, r * e_loc, e_loc, axis=0)
    out_loc = _expert_ffn(params["experts"], my)
    # place the local slice back at its global offset, zero elsewhere
    out_buf = jnp.zeros((e, cap, d), out_loc.dtype)
    out_buf = jax.lax.dynamic_update_slice_in_dim(out_buf, out_loc,
                                                  r * e_loc, axis=0)
    out = _combine(out_buf, slot, order, keep, top_w, t, d, k)
    out = jax.lax.psum(out, tensor_axis)
    dropped = 1.0 - keep.astype(jnp.float32).mean()
    return out, aux, dropped


def moe_forward(params, x, cfg: MoEConfig,
                ctx: Optional[ParallelCtx] = None,
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, D) -> (out (B, S, D), metrics {aux_loss, dropped}).

    Shared experts (deepseek) run as a dense gated FFN added to the routed
    output — they never enter the dispatch machinery.
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)

    if ctx is None or ctx.tensor is None or ctx.tensor_size == 1:
        out, aux, dropped = _moe_local(params, tokens, cfg)
    else:
        tp = ctx.tensor_size
        mesh, ax = ctx.mesh, ctx.tensor
        bspec = P(ctx.batch) if ctx.batch else P(None)
        pspec = {
            "router": P(),
            "experts": jax.tree.map(lambda _: P(ax, None, None),
                                    params["experts"]),
        }
        in_params = {"router": params["router"],
                     "experts": params["experts"]}
        all_axes = tuple(ctx.batch) + (ax,)

        def finalize(o, tk, a, dr):
            a = functools.reduce(lambda v, n: jax.lax.pmean(v, n),
                                 all_axes, a)
            dr = functools.reduce(lambda v, n: jax.lax.pmean(v, n),
                                  all_axes, dr)
            return o.reshape(tk.shape), a, dr

        if cfg.n_experts % tp == 0 and s % tp == 0 and s >= tp:
            # sequence-sharded EP (train / prefill)
            def ep(p, tk):
                o, a, dr = _moe_ep_body(p, tk.reshape(-1, d), cfg=cfg,
                                        tensor_axis=ax, tp=tp)
                return finalize(o, tk, a, dr)
            fn = jax.shard_map(
                ep, mesh=mesh,
                in_specs=(pspec, P(ctx.batch, ax, None)),
                out_specs=(P(ctx.batch, ax, None), P(), P()),
                check_vma=False)
            out, aux, dropped = fn(in_params, x)
        elif cfg.n_experts % tp == 0 and getattr(ctx, "inference", False):
            # §Perf H8: weights-stationary decode — tokens fully
            # replicated, expert FFN hidden dim sharded over the batch
            # axes, one token-sized psum over the mesh
            fsdp = tuple(a for a in ctx.batch)
            pspec_inf = {
                "router": P(),
                "experts": {
                    "w_up": P(ax, None, fsdp if fsdp else None),
                    "w_gate": P(ax, None, fsdp if fsdp else None),
                    "w_down": P(ax, fsdp if fsdp else None, None),
                },
            }

            def sta(p, tk):
                o, a, dr = _moe_stationary_body(
                    p, tk.reshape(-1, d), cfg=cfg, all_axes=all_axes,
                    tensor_axis=ax, tp=tp)
                return o.reshape(tk.shape), a, dr
            fn = jax.shard_map(
                sta, mesh=mesh,
                in_specs=(pspec_inf, P(None, None, None)),
                out_specs=(P(None, None, None), P(), P()),
                check_vma=False)
            out, aux, dropped = fn(in_params, x)
        elif cfg.n_experts % tp == 0:
            # replicated dispatch (decode)
            def rep(p, tk):
                o, a, dr = _moe_replicated_body(p, tk.reshape(-1, d),
                                                cfg=cfg, tensor_axis=ax,
                                                tp=tp)
                return finalize(o, tk, a, dr)
            fn = jax.shard_map(
                rep, mesh=mesh,
                in_specs=(pspec, P(ctx.batch, None, None)),
                out_specs=(P(ctx.batch, None, None), P(), P()),
                check_vma=False)
            out, aux, dropped = fn(in_params, x)
        else:                       # experts not divisible by the TP axis
            out, aux, dropped = _moe_local(params, tokens, cfg)

    out = out.reshape(b, s, d)
    metrics = {"aux_loss": aux * cfg.router_aux_weight, "dropped": dropped}

    if "shared" in params:
        sh = params["shared"]
        dt = x.dtype
        g = jnp.einsum("bsd,df->bsf", x, sh["w_gate"].astype(dt))
        u = jnp.einsum("bsd,df->bsf", x, sh["w_up"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                               sh["w_down"].astype(dt))
    return out, metrics
