"""Backend compiles (persistent-cache loads included) reported by
jax.monitoring between the window's first send and its last answer."""


def read(ctx):
    return ctx.compiles.between(ctx.window["t_begin"], ctx.window["t_end"])
