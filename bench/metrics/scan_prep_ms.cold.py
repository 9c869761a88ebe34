"""Host self time of scan prep (row mask, group and bin discovery of one
(query, shard) slot: the program's repro.scan.prep span) in the traced
window, per query that no cache answered, in ms."""

from program_spans import per_cold_query, self_ms


def read(ctx):
    return per_cold_query(ctx, lambda red: self_ms(red, "repro.scan.prep"))
