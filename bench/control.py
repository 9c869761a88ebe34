#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
program's place, computed in bfloat16 (the precision below the float32
that the configuration states), must come out not correct.

  python3 bench/control.py --workload paper4.explore_cold --seeds 1 2 3

For each seed it builds the cell's capture, answers the queries a run
of that seed would send first (``--per-session`` of each session's
window stream, or the hot pool), once in float64 and once with every
value rounded to bfloat16, compares the two with the run's own
comparison, and prints one JSON line per seed with the numbers compared.
For the live cell it replays the first 300 batches one tick each and
compares the fence after every tick. It needs no chip and never imports
JAX; the benchmark's runs do not run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

import reference  # noqa: E402
import workload  # noqa: E402


def control_queries(ctx: Dict, seed: int, per_session: int,
                    plan: "reference.Plan") -> List[Dict]:
    mix, config = ctx["mix"], ctx["config"]
    t_end = plan.t_start + plan.n * plan.interval
    n_ranks = int(config["n_ranks"])
    if mix.get("pool"):
        return workload.hot_pool(mix, seed, plan.t_start, t_end, n_ranks)
    out = []
    for s in range(int(mix["sessions"])):
        gen = workload.session_queries(mix, seed, workload.WINDOW, s,
                                       plan.t_start, t_end, n_ranks)
        out += [next(gen) for _ in range(per_session)]
    return out


def control_numbers(ctx: Dict, seed: int, per_session: int = 5,
                    precision: str = "bfloat16") -> Dict:
    from store import make_dataset
    gen = ctx["config"]["generation"]
    ds = make_dataset(ctx["config"], seed)
    table, plan = reference.build_table(
        ds.traces, int(gen["interval_ns"]), int(gen["join_window_ns"]),
        int(gen["join_cap"]), int(ctx["config"]["n_ranks"]))
    numbers = reference.empty_numbers()
    notes: List[str] = []
    queries = control_queries(ctx, seed, per_session, plan)
    for spec in queries:
        want = reference.answer(table, plan, spec)
        got = reference.answer(table, plan, spec, precision=precision)
        reference.compare(got, want, numbers, notes,
                          tag=f"query {json.dumps(spec)}")
    return {"seed": seed, "precision": precision, "queries": len(queries),
            "numbers": numbers, "correct": reference.verdict(numbers)}


def control_live(ctx: Dict, seed: int, n_batches: int = 300,
                 precision: str = "bfloat16") -> Dict:
    """The live cell's control: the reference's fence after each of the
    first ``n_batches`` batches (one tick each), with every k_stall
    value rounded to ``precision``, against the float64 reference."""
    import copy

    import live
    from store import make_dataset
    config, mix = ctx["config"], ctx["mix"]
    gen = config["generation"]
    w, cap = int(gen["join_window_ns"]), int(gen["join_cap"])
    ds = make_dataset(config, seed)
    t_first = min(int(tr.kernels.start.min()) for tr in ds.traces)
    t_last = max(int(tr.kernels.end.max()) for tr in ds.traces)
    orders = live.split_capture(ds.traces, workload.live_cuts(
        mix, t_first, t_last))
    seed_traces = [live._rank_part(tr, o, 0)
                   for tr, o in zip(ds.traces, orders)]
    table, plan = reference.build_table(
        seed_traces, int(gen["interval_ns"]), w, cap, int(config["n_ranks"]))
    ref = reference.LiveStore(table, plan)
    ctl = reference.LiveStore(table, copy.copy(plan), precision=precision)
    numbers = reference.empty_numbers(reference.LIVE_LIMITS)
    n = min(n_batches, len(orders[0]["k_ends"]) - 1)
    for i in range(1, n + 1):
        rows, max_end = [], 0
        for r, (tr, o) in enumerate(zip(ds.traces, orders)):
            k = o["k_order"][o["k_ends"][i - 1]:o["k_ends"][i]]
            m_new = o["m_order"][o["m_ends"][i - 1]:o["m_ends"][i]]
            m_old = o["m_order"][:o["m_ends"][i - 1]]
            got, end = reference.append_rows(
                live.kernel_columns(tr, k), live.memcpy_columns(tr, m_new),
                live.memcpy_columns(tr, m_old), r, w, cap)
            if got is not None:
                rows.append(got)
                max_end = max(max_end, end)
        if rows:
            new = {c: np.concatenate([x[c] for x in rows])
                   for c in ("k_start", "k_stall")}
            ref.append(new, max_end)
            ctl.append(new, max_end)
        want = ref.fences()
        got_set, got_hi = ctl.fences()[0]
        if got_set not in {s for s, _ in want}:
            numbers["fence_mismatch"] += 1
        if got_hi not in {h for _, h in want}:
            numbers["hi_fence_mismatch"] += 1
    reference.compare(ctl.answer(), ref.answer(), numbers, [],
                      tag="final fence query")
    return {"seed": seed, "precision": precision, "ticks": n,
            "numbers": numbers,
            "correct": reference.verdict(numbers, reference.LIVE_LIMITS)}


def main(argv=None) -> int:
    import run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--per-session", type=int, default=5)
    args = ap.parse_args(argv)
    ctx = run.load_cell(run.load_benchmark(), args.workload)
    failed_as_it_must = True
    for seed in args.seeds:
        t0 = time.monotonic()
        if ctx["mix"]["kind"] == "live":
            out = control_live(ctx, seed)
        else:
            out = control_numbers(ctx, seed, args.per_session)
        out["seconds"] = time.monotonic() - t0
        failed_as_it_must &= not out["correct"]
        print(json.dumps(out), flush=True)
    return 0 if failed_as_it_must else 1


if __name__ == "__main__":
    sys.exit(main())
