"""Share of the HBM roofline reached by the moments reduce program in the
traced window, in %: the bytes its calls must move (``costs.reduce_bytes``
of the shapes each repro.reduce.dispatch span carries) over the device
time of the moments_rank_fn programs times the chip's HBM bandwidth."""

from program_spans import roofline


def read(ctx):
    return roofline(ctx, "moments", "moments_rank_fn")
