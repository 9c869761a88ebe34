"""Bytes uploaded to the device for the reduces (the bytes stat of the
program's repro.reduce.h2d spans that start in the traced window), per
query that no cache answered, in MB (10^6 bytes)."""

from program_spans import per_cold_query


def read(ctx):
    return per_cold_query(ctx, lambda red: red["names"].get(
        "repro.reduce.h2d", {}).get("stats", {}).get("bytes", 0) / 1e6)
