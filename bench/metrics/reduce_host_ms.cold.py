"""Host self time in front of the device reduce, per query that no cache
answered, in ms: the flat segment space and device partition
(repro.reduce.stage), the uploads (repro.reduce.h2d) and the collective
calls (repro.reduce.dispatch), compiles left out as their children."""

from program_spans import per_cold_query, self_ms

SPANS = ("repro.reduce.stage", "repro.reduce.h2d", "repro.reduce.dispatch")


def read(ctx):
    return per_cold_query(ctx, lambda red: self_ms(red, *SPANS))
