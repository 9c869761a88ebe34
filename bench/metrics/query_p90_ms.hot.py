"""90th percentile (nearest rank) of the latency of every query answered
in the window, in ms, taken at the client."""

from workload import nearest_rank


def read(ctx):
    lat = [(r["t_done"] - r["t_send"]) * 1e3 for r in ctx.done]
    return nearest_rank(lat, 0.90)
