"""Benchmark harness: one module per paper table/figure + kernel/analyzer
micro-benches. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only fig1c]
"""

from __future__ import annotations

import argparse
import sys
import traceback

from repro.compile_cache import enable_compile_cache

from . import (analyzer_scale, fig1a_stall_timeline, fig1b_variability,
               fig1c_scaling, kernels_bench, multimetric_bench, table1_join)

MODULES = {
    "table1": table1_join,
    "fig1a": fig1a_stall_timeline,
    "fig1b": fig1b_variability,
    "fig1c": fig1c_scaling,
    "kernels": kernels_bench,
    "analyzer": analyzer_scale,
    "multimetric": multimetric_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys "
                         f"(default: all of {list(MODULES)})")
    args = ap.parse_args()
    enable_compile_cache()
    keys = args.only.split(",") if args.only else list(MODULES)

    print("name,us_per_call,derived")
    failed = []
    for k in keys:
        try:
            for row in MODULES[k].run():
                print(row.csv())
                sys.stdout.flush()
        except Exception:
            failed.append(k)
            traceback.print_exc()
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
