"""Time an append spends reading the rank DBs (the program's
repro.append.read spans: per-source SQLite reads and the kernel-name
refresh), per ingest tick that started in the traced window, in ms."""

from program_spans import duration_ms, per_ingest_tick


def read(ctx):
    return per_ingest_tick(ctx, lambda red: duration_ms(
        red, "repro.append.read"))
