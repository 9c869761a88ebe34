"""Time an append spends writing the store: staging every extended shard
(repro.append.stage, its shard reads included) and the journal, renames
and manifest of the commit (repro.append.commit), per ingest tick that
started in the traced window, in ms."""

from program_spans import duration_ms, per_ingest_tick


def read(ctx):
    return per_ingest_tick(ctx, lambda red: duration_ms(
        red, "repro.append.stage") + duration_ms(red, "repro.append.commit"))
