"""Exposed cross-chip collective time per answered query, in ms: the
parts of all-reduce, all-gather and reduce-scatter operations during
which no other operation ran on that device, summed over the chips."""

from trace_reduce import COLLECTIVE_PREFIXES


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.done:
        return None
    if not any(n.startswith(COLLECTIVE_PREFIXES) for n in t["by_op_ns"]):
        return None
    return t["exposed_collective_ns"] / 1e6 / len(ctx.done)
