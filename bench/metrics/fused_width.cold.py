"""Mean fused width (query lanes in the tick) that the window's answered
queries rode, from each response's ``tick.fused_width``."""


def read(ctx):
    w = [r["fused_width"] for r in ctx.done]
    return sum(w) / len(w) if w else None
