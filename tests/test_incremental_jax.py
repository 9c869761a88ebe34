"""Incremental SPMD (jax) backend tests: after an append, the device
collectives run only over dirty shards' raw events (asserted through the
store's IO counters), clean shards re-enter as cached device partials,
and the delta result is bit-identical to a cold full jax aggregation —
including on a multi-device mesh, where the slot-wise device partition
is what keeps each shard's partial a pure function of its own rows."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (GenerationConfig, PipelineConfig, SyntheticSpec,
                        TraceStore, VariabilityPipeline, append_rank_db,
                        generate_synthetic, run_aggregation, run_append,
                        run_generation, trace_remainder, truncate_trace,
                        write_rank_db)
from repro.core.tracestore import pack_filename

METRICS = ["k_stall", "m_duration"]
SUITE = ("moments", "quantile")
_NS = 1_000_000_000
STAT_FIELDS = ("count", "sum", "sumsq", "min", "max")


@pytest.fixture(scope="module")
def grown_store(tmp_path_factory):
    """A store built from 30 s snapshots, its DBs grown to the full 40 s,
    appended — with a jax base aggregation populating device partials
    BEFORE the growth (the online-loop state a delta starts from)."""
    spec = SyntheticSpec(n_ranks=2, kernels_per_rank=4000,
                         memcpys_per_rank=600, duration_s=40.0,
                         n_anomaly_windows=2, seed=11)
    ds = generate_synthetic(spec)
    t0 = int(ds.traces[0].kernels.start.min())
    cutoff = (t0 // _NS) * _NS + 30 * _NS
    work = tmp_path_factory.mktemp("jax_inc")
    paths = [str(work / f"rank{tr.rank}.sqlite") for tr in ds.traces]
    for tr, p in zip(ds.traces, paths):
        write_rank_db(p, truncate_trace(tr, cutoff))
    out = str(work / "store")
    run_generation(paths, out, n_ranks=2)
    base = run_aggregation(TraceStore(out), metrics=METRICS,
                           group_by="m_kind", reducers=SUITE,
                           backend="jax")
    assert base.partial_hits == 0
    for tr, p in zip(ds.traces, paths):
        append_rank_db(p, trace_remainder(tr, cutoff))
    rep = run_append(paths, out)
    assert rep.n_new_shards > 0
    return out, rep


def _assert_results_equal(a, b):
    for f in STAT_FIELDS:
        np.testing.assert_array_equal(getattr(a.grouped, f),
                                      getattr(b.grouped, f))
    np.testing.assert_array_equal(a.group_keys, b.group_keys)
    np.testing.assert_array_equal(a.reduced["quantile"].counts,
                                  b.reduced["quantile"].counts)
    assert set(a.copy_kind_bytes) == set(b.copy_kind_bytes)
    for k in a.copy_kind_bytes:
        np.testing.assert_array_equal(a.copy_kind_bytes[k],
                                      b.copy_kind_bytes[k])


def _cold(store_root):
    cold_store = TraceStore(store_root)
    cold_store.clear_summaries()
    cold_store.clear_partials()
    return run_aggregation(cold_store, metrics=METRICS, group_by="m_kind",
                           reducers=SUITE, backend="jax")


def test_jax_delta_bit_identical_to_cold(grown_store):
    """The acceptance criterion: the jax delta (clean shards from cached
    device partials, collectives over dirty rows only) matches a cold
    full jax aggregation bit for bit — moments, quantile sketch and
    transfer-kind bytes."""
    out, rep = grown_store
    delta = run_aggregation(TraceStore(out), metrics=METRICS,
                            group_by="m_kind", reducers=SUITE,
                            backend="jax")
    assert not delta.from_cache
    assert delta.partial_hits > 0
    cold = _cold(out)
    assert cold.partial_hits == 0
    assert len(cold.recomputed_shards) > len(delta.recomputed_shards)
    _assert_results_equal(delta, cold)


def test_jax_delta_reads_only_dirty_shards(grown_store):
    """io_counts assertion: the collectives receive only dirty/new
    shards' raw events — clean shards are served from the float32
    partial namespace without a single shard-file read."""
    out, rep = grown_store
    _cold(out)                       # repopulate every device partial
    # dirty ONE pre-existing shard by rewriting it in place
    store = TraceStore(out)
    cols = store.read_shard(3)
    cols["k_stall"] = cols["k_stall"] + 1.0
    store.write_shard(3, cols)
    store.clear_summaries()

    fresh = TraceStore(out)
    n_shards = len(fresh.shard_indices())
    delta = run_aggregation(fresh, metrics=METRICS, group_by="m_kind",
                            reducers=SUITE, backend="jax")
    assert delta.recomputed_shards == [3]
    assert delta.partial_hits == n_shards - 1
    assert fresh.io_counts["shard_reads"] == 1   # ONLY the dirty shard
    assert fresh.io_counts["partial_reads"] == n_shards - 1
    assert fresh.io_counts["partial_writes"] == 1


def test_jax_device_partials_never_serve_exact_host_path(grown_store):
    """Precision namespacing: a store full of float32 device partials
    must look entirely DIRTY to the exact host aggregation (and vice
    versa) — float32 collective output can never be merged into a
    result a caller expects exact float64 moments from."""
    out, _ = grown_store
    _cold(out)                       # device partials for every shard
    host = run_aggregation(TraceStore(out), metrics=METRICS,
                           group_by="m_kind", reducers=SUITE)
    assert host.partial_hits == 0    # nothing served across namespaces
    assert len(host.recomputed_shards) > 0


def test_jax_corrupt_device_partial_falls_back_to_rescan(grown_store):
    """A torn/corrupt device-partial file is a MISS, not a crash: the
    shard is reclassified dirty, its rows re-reduced on device, and the
    result still matches a cold run bit for bit."""
    out, _ = grown_store
    cold = _cold(out)                # device partials for every shard
    store = TraceStore(out)
    plan = cold.plan
    qkey = store.partial_key((plan.t_start, plan.t_end, plan.n_shards),
                             METRICS, "m_kind", precision="float32",
                             reducers=("moments", "quantile"))
    assert store.has_partial(5, qkey)
    path = os.path.join(store.root, pack_filename(5))
    with open(path, "wb") as f:
        f.write(b"torn device partial pack")
    store.clear_summaries()
    again = run_aggregation(TraceStore(out), metrics=METRICS,
                            group_by="m_kind", reducers=SUITE,
                            backend="jax")
    assert again.recomputed_shards == [5]
    _assert_results_equal(again, cold)


def test_pipeline_append_jax_backend_is_incremental(tmp_path):
    """VariabilityPipeline.append on the jax backend: only dirty/new
    shards recomputed, refreshed result identical to a cold jax
    re-analysis of the same store."""
    spec = SyntheticSpec(n_ranks=2, kernels_per_rank=3000,
                         memcpys_per_rank=500, duration_s=30.0, seed=4)
    ds = generate_synthetic(spec)
    t0 = int(ds.traces[0].kernels.start.min())
    cutoff = (t0 // _NS) * _NS + 22 * _NS
    paths = [str(tmp_path / f"rank{tr.rank}.sqlite") for tr in ds.traces]
    for tr, p in zip(ds.traces, paths):
        write_rank_db(p, truncate_trace(tr, cutoff))
    cfg = PipelineConfig(n_ranks=2, backend="jax", metrics=METRICS,
                         group_by="m_kind", reducers=SUITE,
                         generation=GenerationConfig())
    pipe = VariabilityPipeline(cfg)
    work = str(tmp_path / "store")
    pipe.run(paths, work)

    for tr, p in zip(ds.traces, paths):
        append_rank_db(p, trace_remainder(tr, cutoff))
    res = pipe.append(paths, work)
    agg = res.aggregation
    assert res.generation.n_new_shards > 0
    assert not agg.from_cache
    assert agg.partial_hits > 0
    n_total = len(TraceStore(work).shard_indices())
    assert len(agg.recomputed_shards) < n_total
    _assert_results_equal(agg, _cold(work))


def test_jax_delta_bit_identical_on_multi_device_mesh(tmp_path):
    """8 fake host devices (subprocess, as in test_distributed): the
    slot-wise device partition hands device d rows [d*n/P, (d+1)*n/P) of
    EVERY shard, so a shard's device partial — and therefore the delta
    merge — is identical whether it is reduced alone or alongside the
    whole store."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent(f"""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import sys
    sys.path.insert(0, {src!r})
    import jax
    import numpy as np
    from repro.core import (Query, SyntheticSpec, TraceStore,
                            append_rank_db, generate_synthetic,
                            run_append, run_generation, run_queries,
                            trace_remainder, truncate_trace, write_rank_db)
    from repro.core.aggregation import DEVICE_DISPATCHES
    NS = 1_000_000_000
    spec = SyntheticSpec(n_ranks=2, kernels_per_rank=2000,
                         memcpys_per_rank=300, duration_s=20.0, seed=5)
    ds = generate_synthetic(spec)
    t0 = int(ds.traces[0].kernels.start.min())
    cutoff = (t0 // NS) * NS + 15 * NS
    d = {str(tmp_path)!r}
    paths = [os.path.join(d, 'r%d.sqlite' % tr.rank) for tr in ds.traces]
    for tr, p in zip(ds.traces, paths):
        write_rank_db(p, truncate_trace(tr, cutoff))
    out = os.path.join(d, 'store')
    run_generation(paths, out, n_ranks=2)
    q = Query(metrics=tuple({METRICS!r}), group_by='m_kind',
              reducers=('moments', 'quantile'))
    devs = jax.devices()
    assert len(devs) == 8

    def agg(store):
        return run_queries(store, [q], backend='jax',
                           devices=devs)[0].result
    agg(TraceStore(out))
    for tr, p in zip(ds.traces, paths):
        append_rank_db(p, trace_remainder(tr, cutoff))
    run_append(paths, out)
    delta = agg(TraceStore(out))
    cs = TraceStore(out)
    cs.clear_summaries(); cs.clear_partials()
    cold = agg(cs)
    assert len(delta.recomputed_shards) < len(cold.recomputed_shards)
    # the mesh held all 8 devices, each with its own section of the rows
    ids = sorted(d.id for d in devs)
    for disp in DEVICE_DISPATCHES:
        for sections in disp.placement.values():
            assert sorted(dev for dev, _, _ in sections) == ids
    for f in ('count', 'sum', 'sumsq', 'min', 'max'):
        np.testing.assert_array_equal(getattr(delta.grouped, f),
                                      getattr(cold.grouped, f))
    np.testing.assert_array_equal(delta.reduced['quantile'].counts,
                                  cold.reduced['quantile'].counts)
    print('OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-1000:], out.stderr[-3000:])
