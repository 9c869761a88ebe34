"""The traffic generator, the sample and the metric arithmetic."""

import collections

import numpy as np

import costs
import workload

T0, T1 = 1_700_000_000_000_000_000, 1_700_000_120_000_000_000


def take(gen, n):
    return [next(gen) for _ in range(n)]


def test_cold_mix_keys_are_distinct_and_in_range():
    mix = workload.load_traffic("explore_cold")
    seen = set()
    for s in range(mix["sessions"]):
        for q in take(workload.session_queries(
                mix, 2**40 + 3, workload.WINDOW, s, T0, T1, 4), 200):
            seen.add(workload.spec_key(q))
            t0, t1 = q["time_window"]
            assert T0 <= t0 < t1 <= T1
            assert 2e9 <= t1 - t0 <= 16e9
            assert 1 <= len(q["metrics"]) <= 3
            assert q.get("group_by") in mix["group_by"]
            for r in q.get("ranks", []):
                assert 0 <= r < 4
            assert set(q.get("transfer_kinds", [])) <= {1, 2, 8}
    assert len(seen) == mix["sessions"] * 200


def test_cold_mix_shares_follow_the_mix():
    mix = workload.load_traffic("explore_cold")
    qs = take(workload.session_queries(mix, 5, workload.WINDOW, 0, T0, T1,
                                       4), 4000)
    p99 = np.mean([q.get("anomaly_score") == "p99" for q in qs])
    ranks = np.mean(["ranks" in q for q in qs])
    kinds = np.mean(["transfer_kinds" in q for q in qs])
    assert abs(p99 - 0.30) < 0.03
    assert abs(ranks - 0.25) < 0.03 and abs(kinds - 0.25) < 0.03
    by_group = collections.Counter(q.get("group_by") for q in qs)
    assert len(by_group) == 5 and min(by_group.values()) > 600


def test_streams_and_seeds_do_not_share_draws():
    mix = workload.load_traffic("explore_cold")

    def first(seed, stream, s):
        return workload.spec_key(next(workload.session_queries(
            mix, seed, stream, s, T0, T1, 4)))
    assert first(7, workload.WINDOW, 0) == first(7, workload.WINDOW, 0)
    assert first(7, workload.WINDOW, 0) != first(7, workload.WARMUP, 0)
    assert first(7, workload.WINDOW, 0) != first(7, workload.WINDOW, 1)
    assert first(7, workload.WINDOW, 0) != first(8, workload.WINDOW, 0)
    assert first(2**33 + 7, workload.WINDOW, 0) != first(7, workload.WINDOW, 0)


def test_hot_mix_draws_zipf_from_its_pool():
    mix = workload.load_traffic("explore_hot")
    pool = [workload.spec_key(q)
            for q in workload.hot_pool(mix, 11, T0, T1, 4)]
    assert len(pool) == 64 and len(set(pool)) == 64
    qs = take(workload.session_queries(mix, 11, workload.WINDOW, 0, T0, T1,
                                       4), 20000)
    rank = {k: i for i, k in enumerate(pool)}
    hits = collections.Counter(rank.get(workload.spec_key(q), -1)
                               for q in qs)
    fresh = hits.pop(-1, 0)
    assert abs(fresh / len(qs) - mix["fresh_share"]) < 0.004
    w = workload.zipf_weights(64, mix["zipf_s"])
    n = len(qs) - fresh
    for r in (0, 1, 5, 20):
        assert abs(hits[r] / n - w[r]) < 4 * np.sqrt(w[r] / n)
    assert hits[0] > hits[1] > hits[5] > hits[20]


def test_sample_keeps_the_longest_and_is_seeded():
    a = workload.sample_indices(3, 100, 10, must=[97, 5])
    assert a == workload.sample_indices(3, 100, 10, must=[97, 5])
    assert len(a) == 10 and 97 in a and 5 in a
    assert a != workload.sample_indices(4, 100, 10, must=[97, 5])
    assert workload.sample_indices(3, 4, 10) == [0, 1, 2, 3]


def test_nearest_rank():
    v = list(range(1, 101))
    assert workload.nearest_rank(v, 0.5) == 50
    assert workload.nearest_rank(v, 0.9) == 90
    assert workload.nearest_rank([3.0], 0.9) == 3.0
    assert workload.nearest_rank([], 0.5) is None


def test_reduce_bytes():
    call = {"reducer": "moments", "rows_padded": 1024, "metrics": 2,
            "n_seg": 128, "devices": 1}
    assert costs.reduce_bytes(call) == 1024 * (4 + 8 + 1) + 128 * 2 * 5 * 4
    q = dict(call, reducer="quantile", devices=4)
    assert costs.reduce_bytes(q) == 1024 * 13 + 4 * 128 * 2 * 384 * 4
    assert costs.reduce_program_ns({"jit_rank_fn": 5.0, "other": 7.0}) == 5.0


def test_every_seed_runs_the_same_shapes():
    """What sets a query's work is the same for every seed and for the
    warm-up; only where it falls moves."""
    mix = workload.load_traffic("explore_cold")

    def shapes(seed, stream):
        out = []
        for q in take(workload.session_queries(mix, seed, stream, 2, T0,
                                               T1, 4), 50):
            t0, t1 = q["time_window"]
            out.append((t1 - t0, tuple(q["metrics"]), q.get("group_by"),
                        q.get("anomaly_score"), "ranks" in q,
                        "transfer_kinds" in q))
        return out
    a = shapes(11, workload.WINDOW)
    assert a == shapes(2**40 + 1, workload.WINDOW)
    assert a == shapes(11, workload.WARMUP)
    assert len(set(a)) > 40
