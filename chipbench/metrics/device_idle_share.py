"""Device: the share of a traced run's device part in which no operation
ran on the chip, 100 * (1 - union of operation intervals / the part)."""
from chipbench.lib import trace

WRAPS = []


def read(ctx):
    if ctx.trace is None or ctx.window_ns is None:
        return None
    a, b = ctx.window_ns
    busy = trace.busy_ns(ctx.trace.ops, ctx.window_ns)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (b - a))
