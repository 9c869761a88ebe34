"""Bytes the exchange between chips has to move, and the links' rate.

A reduce collective on a mesh of P chips (the stats of a
``repro.reduce.dispatch`` span: ``reducer``, ``metrics``, ``n_seg``,
``devices``) ends with every chip holding the whole reduced table: 5
float32 moments, or 384 float32 sketch buckets, per (segment, metric),
the widths of ``costs``. Whatever implements it (``psum_scatter`` then
``all_gather``, an all-reduce of the whole table plus a slice, a
``pmin``/``pmax``), it is an all-reduce of that table of T bytes, and
the least any all-reduce must move over the links is a ring's: each chip
sends 2 (P - 1) / P T, half in the reduce-scatter and half in the
all-gather. Summed over the P chips that is 2 (P - 1) T. On one chip
nothing crosses a link.

The links' rate: ``trace_reduce.PEAKS``' ``ici_bits_per_s`` is one
chip's chip-to-chip interconnect, all its links together (1,600 Gbit/s
on a TPU v5e); over 8 it is bytes per second. The device time of the
exchange's operations, summed over the chips, times that rate is the
most the links could have carried in it.
"""

from __future__ import annotations

from typing import Dict

import costs
import trace_reduce as tr

# the exchange's device operations by name: the HLO collectives, and the
# all-reduces jax names for its min/max ops (a CPU trace spells the
# collectives with underscores)
EXCHANGE_PREFIXES = tr.COLLECTIVE_PREFIXES + ("pmin", "pmax")


def table_bytes(call: Dict) -> int:
    """Bytes of the reduced table one chip holds after the call."""
    width = (costs.MOMENT_FIELDS if call["reducer"] == "moments"
             else costs.SKETCH_BUCKETS)
    return int(call["n_seg"]) * int(call["metrics"]) * width * 4


def collective_bytes(call: Dict) -> int:
    """Bytes the call's all-reduce must move over the links at least,
    summed over the chips: 2 (P - 1) times the table."""
    return 2 * (int(call["devices"]) - 1) * table_bytes(call)


def exchange_ns(trace: Dict) -> float:
    """Device time of the exchange's operations, summed over the chips."""
    return float(sum(v for k, v in trace["by_op_ns"].items()
                     if k.replace("_", "-").startswith(EXCHANGE_PREFIXES)))


def ici_bytes_per_s(device_kind: str) -> float:
    return tr.peaks_for(device_kind)["ici_bits_per_s"] / 8
