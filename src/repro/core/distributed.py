"""JAX backend for the paper's collaborative analysis (rank = device).

Hardware adaptation (DESIGN.md §2): the paper's MPI ranks exchanging partial
statistics become mesh devices exchanging via ICI collectives:

  - per-rank binning/moments  -> `shard_map` over the mesh "data" axis; each
    device bins ITS shard of the event stream (block partitioning: the
    device's shard is a contiguous slice, exactly like the paper's ranks),
  - round-robin collaborative stats -> `psum_scatter` (each device reduces
    the bins it OWNS — cyclic ownership, the round-robin), then
    `all_gather` to rebuild the global table. On TPU, psum_scatter+all_gather
    is strictly cheaper than all-devices-all-bins `psum` for large bin
    tables: each link carries 1/P of the table instead of all of it.
  - min/max have no psum_scatter; they ride an `all_reduce`-style `pmin`/
    `pmax` (these are latency-bound; the heavy sum/sumsq take the scatter
    path).

Incremental engine note: since PR 4 this backend is incremental like the
host ones — the collectives run only over DIRTY shards' raw events. The
unit of collective work is a FLAT segment space (the ragged concatenation
of every dirty shard's touched ``(bin, group)`` cells), so one device
dispatch serves any number of dirty shards, and the post-segment-reduce
tensors sliced back per shard are the *device partials* the aggregation
layer caches in the TraceStore (``precision="float32"`` namespace; see
:func:`repro.core.aggregation.compute_partials_jax`). Clean shards never
reach a device — their cached partials re-enter through the host
``merge_at`` path. The summary cache stays keyed ``precision="float32"``
so jax results are never served where exact float64 moments are expected.

Public entry points:

  * :func:`binstats_local` — pure-jnp per-device moments (also the oracle
    for the Pallas binstats kernel),
  * :func:`distributed_binstats` — full shard_map pipeline over a 1-D mesh
    axis; exactly equal to the serial result (property-tested),
  * :func:`distributed_moments_flat` / :func:`distributed_histogram_flat`
    — the dirty-only collective entry points over an arbitrary flat
    segment space (what the incremental jax driver calls); the grouped
    forms below are thin reshapes over them,
  * :func:`distributed_histogram_grouped` — the quantile reducer's
    log-bucket histogram counts; purely additive, so they ride the same
    psum_scatter/all_gather round-robin path as count/sum/sumsq.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .reducers import N_BUCKETS, SUBDIV, V_FLOOR

STATS = 5   # count, sum, sumsq, min, max

_NEG_CAP = -3.4e38   # sentinel instead of inf: survives bf16/psum paths
_POS_CAP = 3.4e38


def binstats_local(bin_ids: jnp.ndarray, values: jnp.ndarray,
                   n_bins: int, valid: Optional[jnp.ndarray] = None,
                   ) -> jnp.ndarray:
    """Per-bin partial moments (n_bins, 5) for one device's samples.

    ``values`` may also be a batched (n_metrics, N) matrix sharing one
    ``bin_ids``/``valid`` vector — the multi-metric single-pass case — in
    which case the result is (n_metrics, n_bins, 5) (vmap over the leading
    metric axis).

    `segment_*` ops lower to sorted-scatter on TPU; the Pallas `binstats`
    kernel replaces this with a one-hot MXU matmul formulation (see
    kernels/binstats) — both satisfy this exact contract.
    """
    if values.ndim == 2:
        return jax.vmap(
            lambda v: binstats_local(bin_ids, v, n_bins, valid=valid)
        )(values)
    v = values.astype(jnp.float32)
    if valid is None:
        valid = jnp.ones(v.shape, dtype=bool)
    bin_ids = jnp.clip(bin_ids, 0, n_bins - 1)
    # invalid rows: weight 0 and neutral elements for min/max
    w = valid.astype(jnp.float32)
    count = jax.ops.segment_sum(w, bin_ids, n_bins)
    s = jax.ops.segment_sum(v * w, bin_ids, n_bins)
    ss = jax.ops.segment_sum(v * v * w, bin_ids, n_bins)
    v_min = jnp.where(valid, v, _POS_CAP)
    v_max = jnp.where(valid, v, _NEG_CAP)
    mn = jax.ops.segment_min(v_min, bin_ids, n_bins)
    mx = jax.ops.segment_max(v_max, bin_ids, n_bins)
    # segments with no rows at all come back as +inf/-inf from segment_min;
    # cap them to the sentinels so downstream collectives stay finite.
    mn = jnp.where(jnp.isfinite(mn), mn, _POS_CAP)
    mx = jnp.where(jnp.isfinite(mx), mx, _NEG_CAP)
    return jnp.stack([count, s, ss, mn, mx], axis=-1)


def merge_stats(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Associative merge of two (n_bins, 5) moment tables."""
    return jnp.stack([
        a[..., 0] + b[..., 0],
        a[..., 1] + b[..., 1],
        a[..., 2] + b[..., 2],
        jnp.minimum(a[..., 3], b[..., 3]),
        jnp.maximum(a[..., 4], b[..., 4]),
    ], axis=-1)


def derive(stats: jnp.ndarray) -> dict:
    """(n_bins,5) moments -> {count,mean,std,min,max} (paper's metrics)."""
    count = stats[..., 0]
    c = jnp.maximum(count, 1.0)
    mean = stats[..., 1] / c
    var = jnp.maximum(stats[..., 2] / c - mean * mean, 0.0)
    occupied = count > 0
    return {
        "count": count,
        "mean": jnp.where(occupied, mean, 0.0),
        "std": jnp.where(occupied, jnp.sqrt(var), 0.0),
        "min": jnp.where(occupied, stats[..., 3], 0.0),
        "max": jnp.where(occupied, stats[..., 4], 0.0),
    }


def _collaborative_sum(vals: jnp.ndarray, axis: str, axis_size: int,
                       dim: int) -> jnp.ndarray:
    """Round-robin additive merge on-mesh along ``dim``.

    `psum_scatter(tiled=True)` gives each device the reduced block of the
    segments it owns (the paper's round-robin ownership); `all_gather`
    rebuilds the full table on every device. On TPU this is strictly
    cheaper than all-devices-all-segments `psum` for large tables: each
    link carries 1/P of the table instead of all of it.

    Pads ``dim`` to a multiple of the axis size for the scatter (the pad
    must be static, so the size is passed in)."""
    n = vals.shape[dim]
    pad = (-n) % axis_size
    pad_width = [(0, 0)] * vals.ndim
    pad_width[dim] = (0, pad)
    padded = jnp.pad(vals, pad_width)
    owned = jax.lax.psum_scatter(padded, axis, scatter_dimension=dim,
                                 tiled=True)
    gathered = jax.lax.all_gather(owned, axis, axis=dim, tiled=True)
    return jax.lax.slice_in_dim(gathered, 0, n, axis=dim)


def _collaborative_reduce(local: jnp.ndarray, axis: str,
                          axis_size: int) -> jnp.ndarray:
    """Round-robin collaborative merge on-mesh.

    The additive channels (count, sum, sumsq) ride
    :func:`_collaborative_sum` along the bin axis. min/max channels are
    made scatter-compatible by negation tricks NOT being valid for min
    (it's not additive) — so they take a `pmin`/`pmax` all-reduce instead
    (these are latency-bound; the heavy sums take the scatter path).

    ``local`` is (n_bins, 5) or, batched over a leading metric axis,
    (n_metrics, n_bins, 5); the scatter/gather always runs along the bin
    axis so all metrics ride one collective.
    """
    bin_axis = local.ndim - 2
    sums_red = _collaborative_sum(local[..., :3], axis, axis_size,
                                  bin_axis)
    mn_red = jax.lax.pmin(local[..., 3], axis)
    mx_red = jax.lax.pmax(local[..., 4], axis)
    return jnp.concatenate(
        [sums_red, mn_red[..., None], mx_red[..., None]], axis=-1)


def distributed_binstats_from_bins(bin_ids: jnp.ndarray,
                                   values: jnp.ndarray, n_bins: int,
                                   mesh: Mesh, axis: str = "data",
                                   valid: Optional[jnp.ndarray] = None,
                                   ) -> jnp.ndarray:
    """Collaborative moments from precomputed bin ids (exact int64 binning
    happens on host — CUPTI ns timestamps overflow int32; see
    :func:`distributed_binstats` for the on-device float32 variant).

    Events arrive block-partitioned: device d holds rows
    [d*n/P, (d+1)*n/P) — contiguous, like the paper's ranks.
    Returns replicated (n_bins, 5) moments.
    """
    def rank_fn(bins, vals, vld):
        local = binstats_local(bins, vals, n_bins, valid=vld)
        return _collaborative_reduce(local, axis, mesh.shape[axis])

    spec = P(axis)
    fn = jax.shard_map(rank_fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=P(), check_vma=False)
    if valid is None:
        valid = jnp.ones(values.shape, dtype=bool)
    return fn(bin_ids, values, valid)


@functools.lru_cache(maxsize=64)
def _moments_flat_fn(n_seg: int, mesh: Mesh, axis: str):
    """Cached jitted collective for :func:`distributed_moments_flat`.

    Eagerly calling a freshly built ``shard_map`` closure re-traces and
    re-compiles on EVERY aggregation (~seconds of fixed cost on CPU —
    enough to drown the incremental win at delta scale). Keying the
    compiled callable on ``(n_seg, mesh, axis)`` and quantizing the
    caller's array shapes (see ``compute_partials_jax``) makes the
    steady-state append→delta loop hit jax's compilation cache instead."""
    def moments_rank_fn(seg, vals, vld):
        local = binstats_local(seg, vals, n_seg, valid=vld)
        return _collaborative_reduce(local, axis, mesh.shape[axis])

    spec = P(axis)
    return jax.jit(jax.shard_map(moments_rank_fn, mesh=mesh,
                                 in_specs=(spec, P(None, axis), spec),
                                 out_specs=P(), check_vma=False))


def distributed_moments_flat(seg_ids: jnp.ndarray, values: jnp.ndarray,
                             n_seg: int, mesh: Mesh, axis: str = "data",
                             valid: Optional[jnp.ndarray] = None,
                             ) -> jnp.ndarray:
    """Collaborative moments over an ARBITRARY flat segment space.

    seg_ids : (N,) int32 precomputed segment ids in [0, n_seg) — any
              host-side fusion of (shard, bin, group) works; the device
              neither knows nor cares what a segment means
    values  : (n_metrics, N) float32 — all metrics share the segment ids

    This is the incremental engine's dirty-only entry point: the jax
    driver concatenates only the DIRTY shards' events, assigns each a
    segment in the ragged per-shard (bin × group) space, and one
    dispatch produces every dirty shard's device partial at once. The
    additive channels ride the psum_scatter/all_gather round-robin; the
    min/max channels the pmin/pmax all-reduce (:func:`_collaborative_reduce`).
    Returns replicated (n_metrics, n_seg, 5) moments.
    """
    if valid is None:
        valid = jnp.ones(seg_ids.shape, dtype=bool)
    return _moments_flat_fn(n_seg, mesh, axis)(seg_ids, values, valid)


@functools.lru_cache(maxsize=64)
def _histogram_flat_fn(n_seg: int, mesh: Mesh, axis: str):
    """Cached jitted collective for :func:`distributed_histogram_flat`
    (same rationale as :func:`_moments_flat_fn`)."""
    n_all = n_seg * N_BUCKETS

    def histogram_rank_fn(seg, vals, vld):
        w = vld.astype(jnp.float32)

        def one_metric(v):
            return jax.ops.segment_sum(
                w, seg * N_BUCKETS + bucketize(v), n_all)

        local = jax.vmap(one_metric)(vals)        # (M, n_all)
        return _collaborative_sum(local, axis, mesh.shape[axis], dim=1)

    spec = P(axis)
    return jax.jit(jax.shard_map(histogram_rank_fn, mesh=mesh,
                                 in_specs=(spec, P(None, axis), spec),
                                 out_specs=P(), check_vma=False))


def distributed_histogram_flat(seg_ids: jnp.ndarray, values: jnp.ndarray,
                               n_seg: int, mesh: Mesh, axis: str = "data",
                               valid: Optional[jnp.ndarray] = None,
                               ) -> jnp.ndarray:
    """Collaborative quantile-sketch histogram counts over an ARBITRARY
    flat segment space (the dirty-only counterpart of
    :func:`distributed_moments_flat` for the ``"quantile"`` reducer).

    Each metric's (segment, bucket) pair is fused into one id; the counts
    are purely additive, so they ride the SAME psum_scatter/all_gather
    round-robin path as the moments' sums. Returns replicated
    (n_metrics, n_seg, N_BUCKETS) counts. The fused id is int32, so
    ``n_seg * N_BUCKETS`` must stay below 2**31 (about 5.59M segments).
    """
    if n_seg * N_BUCKETS > np.iinfo(np.int32).max:
        raise ValueError(
            f"{n_seg:,} segments x {N_BUCKETS} buckets overflows the "
            "int32 fused histogram id")
    if valid is None:
        valid = jnp.ones(seg_ids.shape, dtype=bool)
    out = _histogram_flat_fn(n_seg, mesh, axis)(seg_ids, values, valid)
    return out.reshape(values.shape[0], n_seg, N_BUCKETS)


def distributed_binstats_grouped(bin_ids: jnp.ndarray,
                                 group_ids: jnp.ndarray,
                                 values: jnp.ndarray, n_bins: int,
                                 n_groups: int, mesh: Mesh,
                                 axis: str = "data",
                                 valid: Optional[jnp.ndarray] = None,
                                 ) -> jnp.ndarray:
    """One-pass multi-metric × group-by collaborative moments.

    bin_ids   : (N,) int32 precomputed time-bin ids (exact int64 binning
                happens on host — CUPTI ns timestamps overflow int32)
    group_ids : (N,) int32 in [0, n_groups) — global group-key index
    values    : (n_metrics, N) float32 — all metrics share the bin/group ids

    The (bin, group) pair is fused into one segment id and the tensor
    rides :func:`distributed_moments_flat` — the dense special case of
    the flat segment space. Returns replicated
    (n_metrics, n_bins, n_groups, 5) moments.
    """
    n_metrics = values.shape[0]
    flat = bin_ids * n_groups + group_ids
    out = distributed_moments_flat(flat, values, n_bins * n_groups, mesh,
                                   axis=axis, valid=valid)
    return out.reshape(n_metrics, n_bins, n_groups, STATS)


def bucketize(values: jnp.ndarray) -> jnp.ndarray:
    """Quantile-sketch log2-bucket index, device-side (float32).

    Same contract as :func:`repro.core.reducers.bucket_of`; float32 log2
    may disagree with the float64 host path on exact bucket boundaries,
    which is within the sketch's stated error bound (the host backends
    stay bit-identical to each other — they share the float64 path).
    """
    v = jnp.maximum(values.astype(jnp.float32), jnp.float32(V_FLOOR))
    idx = jnp.floor(jnp.log2(v) * SUBDIV).astype(jnp.int32)
    return jnp.clip(idx, 0, N_BUCKETS - 1)


def distributed_histogram_grouped(bin_ids: jnp.ndarray,
                                  group_ids: jnp.ndarray,
                                  values: jnp.ndarray, n_bins: int,
                                  n_groups: int, mesh: Mesh,
                                  axis: str = "data",
                                  valid: Optional[jnp.ndarray] = None,
                                  ) -> jnp.ndarray:
    """One-pass multi-metric × group-by collaborative quantile-sketch
    histogram (the ``"quantile"`` reducer's collective path).

    bin_ids   : (N,) int32 precomputed time-bin ids (host int64 binning)
    group_ids : (N,) int32 in [0, n_groups)
    values    : (n_metrics, N) float32 — all metrics share bin/group ids

    Each metric's (bin, group, bucket) triple is fused into one segment id
    and the counts ride :func:`distributed_histogram_flat` — the dense
    special case of the flat segment space. Returns replicated
    (n_metrics, n_bins, n_groups, N_BUCKETS) counts.
    """
    n_metrics = values.shape[0]
    flat_bg = bin_ids * n_groups + group_ids
    out = distributed_histogram_flat(flat_bg, values, n_bins * n_groups,
                                     mesh, axis=axis, valid=valid)
    return out.reshape(n_metrics, n_bins, n_groups, N_BUCKETS)


def distributed_binstats(rel_timestamps: jnp.ndarray, values: jnp.ndarray,
                         total_ns: float, n_bins: int,
                         mesh: Mesh, axis: str = "data",
                         valid: Optional[jnp.ndarray] = None,
                         ) -> jnp.ndarray:
    """Fused on-device binning + collaborative moments.

    CONTRACT: ``rel_timestamps`` are float32 nanoseconds RELATIVE to the
    dataset start (the int64 -> relative conversion is exact on host).
    Bin = floor(rel * n_bins / total) clipped to [0, n_bins). The Pallas
    binstats kernel implements this same contract (see kernels/binstats).
    """
    inv_width = np.float32(n_bins / total_ns)

    def rank_fn(ts, vals, vld):
        bins = jnp.clip((ts * inv_width).astype(jnp.int32), 0, n_bins - 1)
        local = binstats_local(bins, vals, n_bins, valid=vld)
        return _collaborative_reduce(local, axis, mesh.shape[axis])

    spec = P(axis)
    fn = jax.shard_map(rank_fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=P(), check_vma=False)
    if valid is None:
        valid = jnp.ones(values.shape, dtype=bool)
    return fn(rel_timestamps, values, valid)


def distributed_iqr(scores: jnp.ndarray, k: float = 1.5) -> dict:
    """IQR fences in pure jax (sort-based percentile), jit-friendly.

    Operates on the replicated per-bin score table (it is tiny compared to
    the event stream — the paper's design point: raw events never leave
    their rank; only O(n_bins) statistics are exchanged).
    """
    occupied = scores != 0.0
    # percentile over occupied bins via sort + linear interpolation
    big = jnp.where(occupied, scores, jnp.inf)
    srt = jnp.sort(big)
    n_occ = jnp.maximum(occupied.sum(), 1)

    def pct(q):
        pos = q * (n_occ - 1).astype(jnp.float32)
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.ceil(pos).astype(jnp.int32)
        frac = pos - lo.astype(jnp.float32)
        vlo = jnp.where(jnp.isfinite(srt[lo]), srt[lo], 0.0)
        vhi = jnp.where(jnp.isfinite(srt[hi]), srt[hi], 0.0)
        return vlo + frac * (vhi - vlo)

    q1, q3 = pct(0.25), pct(0.75)
    iqr = q3 - q1
    hi_fence = q3 + k * iqr
    lo_fence = q1 - k * iqr
    return {"q1": q1, "q3": q3, "iqr": iqr,
            "lo_fence": lo_fence, "hi_fence": hi_fence,
            "flags": scores > hi_fence}


def top_k_anomalies(scores: jnp.ndarray, hi_fence: jnp.ndarray,
                    top_k: int = 5) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Ranked top-k fence exceedances: (values, bin indices)."""
    exceed = jnp.where(scores > hi_fence, scores - hi_fence, -jnp.inf)
    return jax.lax.top_k(exceed, top_k)
