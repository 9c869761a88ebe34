"""Host time of summary-cache reads (the program's repro.summary.read
span, memo hits and misses included) in the traced window, per query
answered from the summary cache, in ms."""

from program_spans import program, self_ms


def read(ctx):
    red = program(ctx)
    hits = sum(1 for r in ctx.done if r["cache_hit"])
    return self_ms(red, "repro.summary.read") / hits if red and hits \
        else None
