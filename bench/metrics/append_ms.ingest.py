"""Mean time of one append (``run_append`` inside an ingest tick: SQLite
read, join, staged shard rewrite and commit) in the traced window, in
ms, from the bench.append span around it."""


def read(ctx):
    w = ctx.window
    d = [s["t1"] - s["t0"] for s in ctx.spans
         if s["name"] == "bench.append"
         and w["t_begin"] <= s["t0"] <= w["t_end"]]
    return 1e3 * sum(d) / len(d) if d else None
