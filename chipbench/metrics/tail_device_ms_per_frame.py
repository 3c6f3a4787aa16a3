"""Edge tail (models/swin.py ``swin_tail``, or ``swin_full`` where the
edge runs the whole model): device ms per frame in the tail program's
executions of the device part, from the trace's modules line.  Nothing is
read unless the part ran one of them per batch: rounds x UEs / batch."""
from chipbench.lib import programs

WRAPS = []
programs.note_profile_dirs()    # before the device part's profiler starts


def read(ctx):
    t = ctx.traffic
    return programs.device_ms_per_frame(ctx, ("swin_tail", "swin_full"),
                                        t.n_ues // t.buckets[-1])
