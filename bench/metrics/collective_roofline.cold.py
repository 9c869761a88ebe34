"""Share of the interconnect's roofline reached by the exchange between
chips in the traced window, in %: the link bytes the reduces' all-reduces
must move (``exchange.collective_bytes`` of the shapes each
repro.reduce.dispatch span on more than one chip carries), over the
device time of the exchange's operations summed over the chips, times
one chip's ICI bandwidth. None where no dispatch spanned chips or the
trace holds no such operation."""

import exchange
from program_spans import program


def read(ctx):
    red = program(ctx)
    if red is None:
        return None
    calls = [st for _, _, st in red["events"].get("repro.reduce.dispatch",
                                                  ())
             if st.get("devices", 1) > 1]
    dev_ns = exchange.exchange_ns(ctx.trace)
    if not calls or dev_ns <= 0:
        return None
    moved = sum(exchange.collective_bytes(st) for st in calls)
    return 100.0 * moved / (dev_ns / 1e9
                            * exchange.ici_bytes_per_s(ctx.device_kind))
