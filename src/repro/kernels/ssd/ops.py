"""Jit'd wrapper for the fused SSD kernel: layout + padding + dispatch.

``ssd_fused`` accepts the model-layout tensors of models/ssm.py
((b, s, H, P) etc.), reshapes to the kernel's head-major layout, pads the
sequence to the chunk multiple (dta=0 padding is the identity step) and
dispatches kernel or oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import ssd_pallas
from .ref import ssd_ref


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel",
                                             "interpret"))
def ssd_fused(xs: jnp.ndarray, dt: jnp.ndarray, A_log: jnp.ndarray,
              B: jnp.ndarray, C: jnp.ndarray, D: jnp.ndarray, *,
              chunk: int = 128, use_kernel: bool = True,
              interpret: bool):
    """Drop-in for models.ssm.ssd_scan: (b,s,H,P) in, (y, state) out."""
    b, s, H, P = xs.shape
    G, N = B.shape[2], B.shape[3]
    hg = H // G

    dtf = dt.astype(jnp.float32)
    A = -jnp.exp(A_log.astype(jnp.float32))
    dta = dtf * A[None, None, :]                          # (b, s, H)
    xbar = dtf[..., None] * xs.astype(jnp.float32)        # (b, s, H, P)

    # head-major: (BH, S, P) / (BG, S, N)
    xbar_h = jnp.moveaxis(xbar, 2, 1).reshape(b * H, s, P)
    dta_h = jnp.moveaxis(dta, 2, 1).reshape(b * H, s)
    B_h = jnp.moveaxis(B, 2, 1).reshape(b * G, s, N)
    C_h = jnp.moveaxis(C, 2, 1).reshape(b * G, s, N)

    pad = (-s) % chunk
    if pad:
        xbar_h = jnp.pad(xbar_h, ((0, 0), (0, pad), (0, 0)))
        dta_h = jnp.pad(dta_h, ((0, 0), (0, pad)))        # dtA=0: identity
        B_h = jnp.pad(B_h, ((0, 0), (0, pad), (0, 0)))
        C_h = jnp.pad(C_h, ((0, 0), (0, pad), (0, 0)))

    fn = ssd_pallas if use_kernel else ssd_ref
    kw = {"interpret": interpret} if use_kernel else {}
    y_h, state_h = fn(xbar_h, dta_h, B_h, C_h, hg=hg, chunk=chunk, **kw)

    y = jnp.moveaxis(y_h[:, :s].reshape(b, H, s, P), 1, 2)
    y = y + D.astype(jnp.float32)[None, None, :, None] * \
        xs.astype(jnp.float32)
    return y.astype(xs.dtype), state_h.reshape(b, H, P, N)
