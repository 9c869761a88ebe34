"""Host self time of rendering answers (repro.render) and encoding and
writing HTTP responses (repro.respond) in the traced window, per answered
query, in ms."""

from program_spans import program, self_ms


def read(ctx):
    red = program(ctx)
    if red is None or not ctx.done:
        return None
    return self_ms(red, "repro.render", "repro.respond") / len(ctx.done)
