"""Jit'd public wrapper for the rolling kernel: padding + dispatch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK, rolling_pallas
from .ref import rolling_ref


@functools.partial(jax.jit, static_argnames=("window", "use_kernel",
                                             "interpret", "block"))
def rolling_stats(x: jnp.ndarray, *, window: int, use_kernel: bool = True,
                  interpret: bool,
                  block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """Trailing-window rolling mean/std: (N,) -> (N, 2)."""
    n = x.shape[0]
    blk = max(block, window)             # kernel requires window <= block
    pad = (-n) % blk
    xp = jnp.concatenate([x.astype(jnp.float32),
                          jnp.zeros((pad,), jnp.float32)])
    if use_kernel:
        out = rolling_pallas(xp, window=window, block=blk,
                             interpret=interpret)
    else:
        out = rolling_ref(xp, window=window)
    return out[:n]
