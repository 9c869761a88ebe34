"""Mean duration of one tick's commit (the program's repro.commit span:
LRU touches and evictions, counters, slot retirement) over the commits
that start in the traced window, in ms."""

from program_spans import program


def read(ctx):
    red = program(ctx)
    rec = red["names"].get("repro.commit") if red else None
    return rec["total_ns"] / 1e6 / rec["count"] if rec and rec["count"] \
        else None
