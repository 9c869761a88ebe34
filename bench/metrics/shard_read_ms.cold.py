"""Host self time of shard file reads (the program's repro.shard.read
span) in the traced window, per query that no cache answered, in ms."""

from program_spans import per_cold_query, self_ms


def read(ctx):
    return per_cold_query(ctx, lambda red: self_ms(red, "repro.shard.read"))
