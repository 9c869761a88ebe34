"""Host self time of the cache writes, partial packs (repro.partials.write)
and summaries (repro.summary.write), in the traced window, per query that
no cache answered, in ms."""

from program_spans import per_cold_query, self_ms


def read(ctx):
    return per_cold_query(ctx, lambda red: self_ms(
        red, "repro.partials.write", "repro.summary.write"))
