"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips, in %."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["window_ns"]:
        return None
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])
