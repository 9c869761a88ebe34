"""Share of the window's answered queries served from the summary cache
(response ``cache_hit``), in %."""


def read(ctx):
    if not ctx.done:
        return None
    return 100.0 * sum(bool(r["cache_hit"]) for r in ctx.done) / len(ctx.done)
