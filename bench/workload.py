"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<name>.json`` and turns them, with a seed, into queries
(the exploration mixes) or a batch schedule (the live mix).

Numpy and the standard library only: the load generator imports this
module in a process that never imports JAX or the program.

Every draw comes from ``numpy.random.default_rng([seed, stream, ...])``
with a fixed stream id per purpose, so the window's queries, the
warm-up's, a hot pool and the correctness sample never share draws.
What sets a query's work (its shape) is drawn from one key for every
seed, so every seed runs the same work, placed elsewhere.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1_000_000_000

WINDOW, WARMUP, POOL, SAMPLE = 1, 2, 3, 4
SHAPES = 0          # the rng key of query shapes, the same for every seed


def load_traffic(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def seed_key(seed: int) -> int:
    """Any whole-number seed as a non-negative rng key."""
    return int(seed) % (1 << 64)


def rng_for(seed: int, stream: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([seed_key(seed), stream, *more])


# --- exploration queries ------------------------------------------------------

def explore_query(shape: np.random.Generator, place: np.random.Generator,
                  mix: Dict, t_start: int, t_end: int, n_ranks: int) -> Dict:
    """One analyst question, as a ``/v1/query`` spec object. ``shape``
    draws what sets the work (window length, metrics, grouping, fence,
    which predicates), ``place`` where it falls (window offset, the
    subsets' members)."""
    lo, hi = mix["window_s"]
    width = int(math.exp(shape.uniform(math.log(lo), math.log(hi))) * NS)
    width = min(width, t_end - t_start)
    names = list(mix["metrics"])
    k = int(shape.integers(mix["n_metrics"][0], mix["n_metrics"][1] + 1))
    metrics = [names[i] for i in sorted(shape.choice(len(names), k,
                                                     replace=False))]
    group_by = mix["group_by"][int(shape.integers(len(mix["group_by"])))]
    p99 = shape.random() < mix["p99_share"]
    ranks = shape.random() < mix["rank_subset_share"]
    kinds = shape.random() < mix["kind_subset_share"]
    t0 = int(t_start + place.integers(0, max(t_end - t_start - width, 1)))
    spec: Dict = {"metrics": metrics, "time_window": [t0, t0 + width]}
    if group_by is not None:
        spec["group_by"] = group_by
    if p99:
        spec["anomaly_score"] = "p99"
    if ranks:
        spec["ranks"] = _subset(place, list(range(n_ranks)))
    if kinds:
        spec["transfer_kinds"] = _subset(place, list(mix["transfer_kinds"]))
    return spec


def _subset(rng: np.random.Generator, items: List[int]) -> List[int]:
    """A non-empty proper subset, uniform over its size."""
    k = int(rng.integers(1, len(items)))
    return sorted(int(x) for x in rng.choice(items, k, replace=False))


def session_queries(mix: Dict, seed: int, stream: int, session: int,
                    t_start: int, t_end: int, n_ranks: int) -> Iterator[Dict]:
    """The endless query sequence of one closed-loop session. The shapes
    of an exploration session's queries are the same for every seed and
    stream (the same work in every run, and the warm-up meets the
    window's shapes); the seed and stream place them."""
    place = rng_for(seed, stream, session)
    if mix.get("pool"):
        pool = hot_pool(mix, seed, t_start, t_end, n_ranks)
        weights = zipf_weights(len(pool), mix["zipf_s"])
        base = load_traffic(mix["base"])
        shape = rng_for(SHAPES, 0, session)
        while True:
            if place.random() < mix.get("fresh_share", 0.0):
                yield explore_query(shape, place, base, t_start, t_end,
                                    n_ranks)
            else:
                yield pool[int(place.choice(len(pool), p=weights))]
    shape = rng_for(SHAPES, 0, session)
    while True:
        yield explore_query(shape, place, mix, t_start, t_end, n_ranks)


def hot_pool(mix: Dict, seed: int, t_start: int, t_end: int,
             n_ranks: int) -> List[Dict]:
    """A hot mix's pool, made by its base mix's generator."""
    base = load_traffic(mix["base"])
    shape, place = rng_for(SHAPES, POOL), rng_for(seed, POOL)
    return [explore_query(shape, place, base, t_start, t_end, n_ranks)
            for _ in range(int(mix["pool"]))]


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** float(s)
    return w / w.sum()


def spec_key(spec: Dict) -> str:
    return json.dumps(spec, sort_keys=True)


# --- the live mix ---------------------------------------------------------------

def live_cuts(mix: Dict, t_first: int, t_last_end: int) -> List[int]:
    """Capture times at which the live writer's batches end: one every
    ``batch_ms`` of capture from ``seed_s`` seconds after the first
    kernel, so batch i holds the events that end in (cut[i-1], cut[i]]
    and is due ``batch_ms`` after batch i-1, at the capture's own rate."""
    step = int(mix["batch_ms"]) * 1_000_000
    first = t_first + int(mix["seed_s"]) * NS
    n = max(1, -(-(t_last_end - first) // step))
    return [first + i * step for i in range(n + 1)]


# --- the correctness sample -------------------------------------------------------

def sample_indices(seed: int, n_done: int, k: int,
                   must: Sequence[int] = ()) -> List[int]:
    """``k`` of ``n_done`` finished requests, drawn from the seed, with
    the ``must`` indices (the longest requests) always in."""
    rng = rng_for(seed, SAMPLE)
    picked = set(int(i) for i in must)
    order = rng.permutation(n_done)
    for i in order:
        if len(picked) >= min(k, n_done):
            break
        picked.add(int(i))
    return sorted(picked)


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    """The type-1 (inverted-CDF) quantile: the smallest value with at
    least a share ``q`` of the values at or below it."""
    if not len(values):
        return None
    v = sorted(values)
    return float(v[max(int(math.ceil(q * len(v))), 1) - 1])
