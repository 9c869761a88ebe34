import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

import pytest  # noqa: E402


def tiny(ctx, kernels=3000, memcpys=400, duration_s=16.0):
    """A cell's context cut to a size a CPU test can hold."""
    ctx = dict(ctx)
    config = dict(ctx["config"], kernels_per_rank=kernels,
                  memcpys_per_rank=memcpys)
    config["generator"] = dict(config["generator"], duration_s=duration_s)
    ctx["config"] = config
    return ctx


def benchmark_with_candidates():
    """BENCHMARK.json with the cells of ``candidate_cells.json`` added:
    cells the harness runs that are not yet proven on the chip."""
    import json

    import run
    bench = run.load_benchmark()
    with open(os.path.join(HERE, "candidate_cells.json")) as f:
        more = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + more[key]
    return bench


@pytest.fixture
def cell():
    """``cell(name)``: the named cell's context at a tiny size."""
    import run

    def make(name, **kw):
        return tiny(run.load_cell(benchmark_with_candidates(), name), **kw)
    return make
