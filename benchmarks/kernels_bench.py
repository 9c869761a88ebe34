"""Analyzer kernel micro-benchmarks: Pallas (interpret) vs pure-jnp ref.

interpret=True timings on CPU measure the *semantics* path, not TPU perf —
the derived events/s column is the throughput denominator used to size
shards; the TPU projection lives in EXPERIMENTS.md §Roofline."""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import binstats, iqr_fences, rolling_stats

from .common import Row, timeit


def run() -> List[Row]:
    rng = np.random.default_rng(0)
    rows: List[Row] = []

    n, n_bins = 65_536, 512
    ts = jnp.asarray(rng.uniform(0, 1e9, n), jnp.float32)
    vals = jnp.asarray(rng.normal(100, 20, n), jnp.float32)
    valid = jnp.ones((n,), bool)
    for use_kernel, tag in ((True, "pallas"), (False, "ref")):
        def go(u=use_kernel):
            binstats(ts, vals, valid, total_ns=1e9, n_bins=n_bins,
                     use_kernel=u, interpret=True).block_until_ready()
        go()
        us = timeit(go, repeat=3)
        rows.append(Row(f"kernels/binstats_{tag}", us,
                        f"{n/us:.1f} Mev/s" if us else ""))

    m = 4096
    scores = jnp.asarray(np.abs(rng.normal(10, 4, m)), jnp.float32)
    occ = scores != 0
    for use_kernel, tag in ((True, "pallas"), (False, "ref")):
        def go(u=use_kernel):
            jax.block_until_ready(
                iqr_fences(scores, occ, use_kernel=u, interpret=True))
        go()
        us = timeit(go, repeat=3)
        rows.append(Row(f"kernels/iqr_{tag}", us, f"bins={m}"))

    k = 32_768
    x = jnp.asarray(rng.normal(0, 1, k), jnp.float32)
    for use_kernel, tag in ((True, "pallas"), (False, "ref")):
        def go(u=use_kernel):
            rolling_stats(x, window=64, use_kernel=u,
                          interpret=True).block_until_ready()
        go()
        us = timeit(go, repeat=3)
        rows.append(Row(f"kernels/rolling_{tag}", us, f"n={k};w=64"))
    return rows
