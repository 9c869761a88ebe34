"""Time an append spends in the window left-join of kernels to memcpys
(the program's repro.append.join spans), per ingest tick that started in
the traced window, in ms."""

from program_spans import duration_ms, per_ingest_tick


def read(ctx):
    return per_ingest_tick(ctx, lambda red: duration_ms(
        red, "repro.append.join"))
