"""The benchmark's data: rank DBs and the trace store, made from a seed.

The capture itself comes from the program's synthetic generator, called
only here (``make_dataset``), so the events a run serves and the events
the plain reference reads are one and the same arrays. Everything else
is the benchmark's: the rank DBs are written one process per rank, and
the store is built through ``VariabilityPipeline.generate`` on the
pipeline's own ``process`` backend. No function here touches JAX, and
the caller must not have initialised it: both steps fork.
"""

from __future__ import annotations

import multiprocessing as mp
import os
from typing import Dict, List, Sequence

_FORK = mp.get_context("fork")


def spec_of(config: Dict, seed: int):
    """The generator's ``SyntheticSpec`` for a configuration file."""
    from repro.core.events import SyntheticSpec
    gen = config["generator"]
    return SyntheticSpec(
        n_ranks=int(config["n_ranks"]),
        kernels_per_rank=int(config["kernels_per_rank"]),
        memcpys_per_rank=int(config["memcpys_per_rank"]),
        n_gpus=int(gen["n_gpus"]), n_streams=int(gen["n_streams"]),
        duration_s=float(gen["duration_s"]),
        n_anomaly_windows=int(gen["n_anomaly_windows"]),
        seed=int(seed))


def make_dataset(config: Dict, seed: int):
    """The capture of one run: every rank's kernels and memcpys."""
    from repro.core.events import generate_synthetic
    return generate_synthetic(spec_of(config, seed))


def _write_one(args) -> None:
    path, trace = args
    from repro.core.events import write_rank_db
    write_rank_db(path, trace)


def write_rank_dbs(traces: Sequence, db_dir: str) -> List[str]:
    """One SQLite DB per rank, each written by its own process."""
    os.makedirs(db_dir, exist_ok=True)
    paths = [os.path.join(db_dir, f"rank{tr.rank}.sqlite") for tr in traces]
    procs = [_FORK.Process(target=_write_one, args=((p, tr),))
             for p, tr in zip(paths, traces)]
    for p in procs:
        p.start()
    for p in procs:
        p.join()
    bad = [p.exitcode for p in procs if p.exitcode != 0]
    if bad:
        raise RuntimeError(f"rank DB writers failed with exit codes {bad}")
    return paths


def build_store(db_paths: Sequence[str], store_dir: str, config: Dict):
    """The trace store, through the pipeline's own generation entry point
    on its ``process`` backend (one worker per generation rank)."""
    from repro.core import PipelineConfig, VariabilityPipeline
    from repro.core.generation import GenerationConfig
    gen = config["generation"]
    pipe = VariabilityPipeline(PipelineConfig(
        n_ranks=int(config["n_ranks"]), backend="process",
        generation=GenerationConfig(
            interval_ns=int(gen["interval_ns"]),
            join_window_ns=int(gen["join_window_ns"]),
            join_cap=int(gen["join_cap"]))))
    return pipe.generate(list(db_paths), store_dir)
