"""Jit'd public wrapper for the IQR kernel: pow-2 padding + dispatch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import iqr_pallas
from .ref import iqr_ref


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length() if n & (n - 1) else max(n, 2)


@functools.partial(jax.jit,
                   static_argnames=("k_factor", "use_kernel", "interpret"))
def iqr_fences(scores: jnp.ndarray, occupied: jnp.ndarray, *,
               k_factor: float = 1.5, use_kernel: bool = True,
               interpret: bool):
    """IQR anomaly detection over a per-bin score table.

    Returns dict with q1/q3/iqr/lo_fence/hi_fence/n_occ scalars and (n,)
    int32 ``flags`` (1 where score exceeds the upper Tukey fence).
    """
    n = scores.shape[0]
    n_p = _next_pow2(n)
    pad = n_p - n
    s = jnp.concatenate([scores.astype(jnp.float32),
                         jnp.zeros((pad,), jnp.float32)])
    o = jnp.concatenate([occupied.astype(bool), jnp.zeros((pad,), bool)])

    fn = iqr_pallas if use_kernel else iqr_ref
    kwargs = {"interpret": interpret} if use_kernel else {}
    srt, flags, stats = fn(s, o, k_factor=k_factor, **kwargs)
    return {
        "sorted": srt[:n], "flags": flags[:n],
        "q1": stats[0], "q3": stats[1], "iqr": stats[2],
        "lo_fence": stats[3], "hi_fence": stats[4], "n_occ": stats[5],
    }
