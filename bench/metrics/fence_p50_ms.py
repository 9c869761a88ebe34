"""Median (nearest rank) over the window's live batches of the time from
when a batch was due to be written to when the subscriber got the first
fence event whose watermarks cover it, in ms."""

from workload import nearest_rank


def read(ctx):
    return nearest_rank(ctx.fence_ms, 0.50)
