"""Jit'd public wrapper for the histbin kernel: padding + dispatch.

``histbin(...)`` pads events to the tile size and bins to the bin tile,
then calls the Pallas kernel (in interpret mode when the caller passes
``interpret=True``; every caller must say) or the jnp reference.
``values`` may be a single (N,) metric — returning the UNPADDED
(n_bins, n_buckets) count table — or a batched (M, N) metric matrix
sharing one timestamp/valid vector, returning
(M, n_bins, n_buckets). Bucket layout matches
:class:`repro.core.reducers.QuantileSketch` (bucket axis last), so the
output drops straight into ``QuantileSketch(counts=...)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.reducers import N_BUCKETS

from ..padding import pad_events
from .kernel import (DEFAULT_BIN_TILE, DEFAULT_EV_TILE, histbin_pallas)
from .ref import histbin_ref


@functools.partial(
    jax.jit, static_argnames=("total_ns", "n_bins", "n_buckets",
                              "use_kernel", "interpret", "ev_tile",
                              "bin_tile"))
def histbin(rel_ts: jnp.ndarray, values: jnp.ndarray,
            valid: jnp.ndarray, *, total_ns: float, n_bins: int,
            n_buckets: int = N_BUCKETS,
            use_kernel: bool = True, interpret: bool,
            ev_tile: int = DEFAULT_EV_TILE,
            bin_tile: int = DEFAULT_BIN_TILE) -> jnp.ndarray:
    """Fused binning + per-bin log-bucket histogram counts.

    rel_ts : (N,) float32 ns relative to dataset start
    values : (N,) or (M, N) float32 metric samples (shared timestamps)
    valid  : (N,) bool
    """
    squeeze = values.ndim == 1
    vals = values[None, :] if squeeze else values
    rel_ts = pad_events(rel_ts.astype(jnp.float32), ev_tile)
    vals = pad_events(vals.astype(jnp.float32), ev_tile)
    valid = pad_events(valid.astype(bool), ev_tile, fill=False)

    if use_kernel:
        n_bins_p = int(np.ceil(n_bins / bin_tile) * bin_tile)
        out = histbin_pallas(rel_ts, vals, valid,
                             total_ns=total_ns, n_bins=n_bins,
                             n_bins_padded=n_bins_p, n_buckets=n_buckets,
                             ev_tile=ev_tile, bin_tile=bin_tile,
                             interpret=interpret)
        # events were clipped to n_bins-1 < n_bins_p, so padding bins are
        # empty by construction; drop them.
        out = out[:, :n_bins]
    else:
        out = histbin_ref(rel_ts, vals, valid, total_ns=total_ns,
                          n_bins=n_bins, n_buckets=n_buckets)
    return out[0] if squeeze else out
