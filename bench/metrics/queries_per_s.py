"""Queries answered per second: every query sent in the window, over the
time from the window's first send to its last answer (sending stops when
the window's time is up; the run then waits for every answer)."""


def read(ctx):
    w = ctx.window
    span = w["t_end"] - w["t_begin"]
    return len(ctx.done) / span if ctx.done and span > 0 else None
