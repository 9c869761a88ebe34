"""Host self time of the per-lane merge and finalize tail (the program's
repro.merge span, its summary write left out) in the traced window, per
query that no cache answered, in ms."""

from program_spans import per_cold_query, self_ms


def read(ctx):
    return per_cold_query(ctx, lambda red: self_ms(red, "repro.merge"))
