"""Host time waiting for each reduce's result and copying it back (self
time of the program's repro.reduce.d2h span) in the traced window, per
query that no cache answered, in ms."""

from program_spans import per_cold_query, self_ms


def read(ctx):
    return per_cold_query(ctx, lambda red: self_ms(red, "repro.reduce.d2h"))
