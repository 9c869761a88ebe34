"""The device collectives compiled for a described (unattached) TPU v5e:
one chip and a 2x2 mesh, at the padded sizes of chip_smoke.py's two
dispatches (its 4-rank store at the paper's density) and of the largest
dispatches of the served eight-rank capture on four chips. The TPU compiler
runs here without the chip, so what it would refuse, or what would not
fit the chip's 16 GB, fails here first.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers import every
test file."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distributed import _histogram_flat_fn, _moments_flat_fn

V5E_HBM_BYTES = 16 * 1024 ** 3
# (collective, padded rows, metrics, quantized segments): the smoke's
# moments-only dispatch (two metrics by device, and a filtered query),
# and its quantile dispatch (one metric, by kernel name), whose suite
# runs the moments collective too
CASES = [("moments", 1 << 23, 2, 640),
         ("moments", 1 << 24, 1, 7808),
         ("histogram", 1 << 24, 1, 7808),
         # the paper8.explore_cold cell's largest moments dispatch and its
         # largest histogram dispatches (by rows, by segments), from the
         # repro.reduce.dispatch stats of a traced run on a 2x2 v5e
         ("moments", 1 << 23, 3, 1920),
         ("histogram", 1 << 22, 3, 768),
         ("histogram", 1 << 21, 3, 1024)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compile(topo, n_dev: int, kind: str, n: int, n_metrics: int,
             n_seg: int):
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    cols = NamedSharding(mesh, P(None, "data"))
    args = (jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows),
            jax.ShapeDtypeStruct((n_metrics, n), jnp.float32,
                                 sharding=cols),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rows))
    make = _moments_flat_fn if kind == "moments" else _histogram_flat_fn
    return make(n_seg, mesh, "data").lower(*args).compile()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("case", CASES)
def test_collective_compiles_for_one_v5e_chip(topo, case):
    compiled = _compile(topo, 1, *case)
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("case", CASES)
def test_collective_compiles_for_four_v5e_chips(topo, case):
    compiled = _compile(topo, 4, *case)
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
    hlo = compiled.as_text()
    # the round-robin reduction across all four devices: psum_scatter
    # then all_gather. The v5e compiler lowers this psum_scatter to an
    # all-reduce plus a slice rather than a reduce-scatter, so either
    # spelling is accepted.
    group = "replica_groups={{0,1,2,3}}"
    reduced = [ln for ln in hlo.splitlines()
               if group in ln and (" reduce-scatter(" in ln
                                   or " all-reduce(" in ln)]
    gathered = [ln for ln in hlo.splitlines()
                if group in ln and " all-gather(" in ln]
    assert reduced and gathered
