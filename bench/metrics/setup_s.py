"""Seconds from the start of the process to the start of the window:
capture, rank DBs, store build, JAX start, service start, compiles and
warm-up."""


def read(ctx):
    return ctx.setup_s
