"""Jit'd wrapper: padding + head-major layout + dispatch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas
from .ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_tile", "kv_tile", "use_kernel", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    q_tile: int = 128, kv_tile: int = 128,
                    use_kernel: bool = True,
                    interpret: bool) -> jnp.ndarray:
    """(B, S, H, hd) model layout in/out; equal q/kv head counts
    (GQA callers expand first — see models/attention H1)."""
    b, s, h, hd = q.shape

    def to_major(t):
        return jnp.moveaxis(t, 2, 1).reshape(b * h, s, hd)
    qm, km, vm = to_major(q), to_major(k), to_major(v)
    pad = (-s) % max(q_tile, kv_tile)
    if pad:
        qm = jnp.pad(qm, ((0, 0), (0, pad), (0, 0)))
        km = jnp.pad(km, ((0, 0), (0, pad), (0, 0)))
        vm = jnp.pad(vm, ((0, 0), (0, pad), (0, 0)))
    if use_kernel:
        om = flash_attention_pallas(
            qm, km, vm, causal=causal, window=window, q_tile=q_tile,
            kv_tile=kv_tile, s_real=s, interpret=interpret)
    else:
        om = flash_attention_ref(qm, km, vm, causal=causal,
                                 window=window, s_real=s)
    om = om[:, :s]
    return jnp.moveaxis(om.reshape(b, h, s, hd), 1, 2)
