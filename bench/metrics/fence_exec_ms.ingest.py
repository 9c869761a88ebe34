"""Time an ingest tick spends on its fences after the append: executing
the fence lanes on the extended store (repro.tick.lanes of ingest ticks)
and diffing and publishing the fence states (repro.fence.publish), per
ingest tick that started in the traced window, in ms."""

from program_spans import duration_ms, per_ingest_tick


def read(ctx):
    return per_ingest_tick(ctx, lambda red: duration_ms(
        red, "repro.tick.lanes", kind="ingest") + duration_ms(
        red, "repro.fence.publish"))
