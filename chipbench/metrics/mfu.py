"""Whole step: model operations of every frame completed in the untraced
part of a traced run's window (the benchmark's count of the whole model,
head and tail, the same for every split) over that part's wall time, as a
share of the chip's peak.  The untraced part runs as a ``--trace 0`` run
does, so the spans' and the profiler's own cost is not in it."""
from chipbench.lib import flops

WRAPS = []


def read(ctx):
    if not ctx.plain_frames or ctx.plain_seconds <= 0:
        return None
    done = flops.total_flops(ctx.shape) * ctx.plain_frames
    return 100.0 * done / ctx.plain_seconds / ctx.peaks["flops_per_s"]
