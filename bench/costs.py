"""Bytes a device reduce has to move, from its shapes.

One call of a reducer's collective (``instrument`` records its shapes)
reads its row inputs once: int32 segment ids, ``metrics`` float32 values
and a bool valid flag per padded row. Every chip then holds the whole
reduced table: 5 float32 moments, or 384 float32 sketch buckets, per
(segment, metric). These are the least bytes the call can move; what
the compiled program moves besides is the program's cost.
"""

from __future__ import annotations

from typing import Dict

MOMENT_FIELDS = 5
SKETCH_BUCKETS = 384
# the device programs of the reduce collectives, by name fragment
REDUCE_PROGRAMS = ("rank_fn",)


def reduce_bytes(call: Dict) -> int:
    rows = int(call["rows_padded"])
    m = int(call["metrics"])
    width = MOMENT_FIELDS if call["reducer"] == "moments" else SKETCH_BUCKETS
    inputs = rows * (4 + 4 * m + 1)
    outputs = int(call["devices"]) * int(call["n_seg"]) * m * width * 4
    return inputs + outputs


def reduce_program_ns(by_module_ns: Dict[str, float]) -> float:
    return float(sum(v for k, v in by_module_ns.items()
                     if any(p in k for p in REDUCE_PROGRAMS)))
