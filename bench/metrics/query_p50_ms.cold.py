"""Median latency of every query answered in the window, in ms, taken at
the client from send to the full response."""

from workload import nearest_rank


def read(ctx):
    lat = [(r["t_done"] - r["t_send"]) * 1e3 for r in ctx.done]
    return nearest_rank(lat, 0.50)
