"""Mean time a query request waited in the admission queue before its
tick was formed, in ms: the queued_ns stat over the requests stat of the
program's repro.tick.exec spans of query ticks in the traced window."""

from program_spans import program, ticks


def read(ctx):
    red = program(ctx)
    if red is None:
        return None
    t = ticks(red, "query")
    n = sum(st.get("requests", 0) for st in t)
    return sum(st.get("queued_ns", 0) for st in t) / 1e6 / n if n else None
