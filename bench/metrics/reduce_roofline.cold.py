"""Share of the HBM roofline reached by the device reduce programs in
the traced window, in %: the bytes the reduces must move (their inputs
once, their outputs on every chip; ``costs.reduce_bytes``) over the
device time of those programs times the chip's HBM bandwidth."""

import costs
from trace_reduce import peaks_for


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    w = ctx.window
    calls = [r for r in ctx.reduces if w["t_begin"] <= r["t"] <= w["t_end"]]
    dev_ns = costs.reduce_program_ns(t["by_module_ns"])
    if not calls or dev_ns <= 0:
        return None
    moved = sum(costs.reduce_bytes(r) for r in calls)
    bw = peaks_for(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * moved / (dev_ns / 1e9 * bw)
