"""Host self time of shard reads and scan prep (the bench.read_shard and
bench.scan_prep spans) in the traced window, per query that was not
answered from a cache, in ms."""

SPANS = ("bench.read_shard", "bench.scan_prep")


def read(ctx):
    cold = [r for r in ctx.done
            if not r["cache_hit"] and not r["inflight_hit"]]
    if ctx.trace is None or not cold:
        return None
    ns = sum(ctx.trace["self_ns"].get(s, 0.0) for s in SPANS)
    return ns / 1e6 / len(cold)
