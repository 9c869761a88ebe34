"""Bytes the reduces put into the exchange between chips (the
exchange_bytes stat of the program's repro.reduce.dispatch spans that
start in the traced window: each chip's reduced table, 0 on one chip),
per query that no cache answered, in MB (10^6 bytes). None where the
stat is absent or no dispatch spanned more than one chip."""

from program_spans import cold_queries, program


def read(ctx):
    red = program(ctx)
    n = cold_queries(ctx)
    if red is None or not n:
        return None
    calls = [st for _, _, st in red["events"].get("repro.reduce.dispatch",
                                                  ())
             if "exchange_bytes" in st and st.get("devices", 1) > 1]
    if not calls:
        return None
    return sum(st["exchange_bytes"] for st in calls) / 1e6 / n
