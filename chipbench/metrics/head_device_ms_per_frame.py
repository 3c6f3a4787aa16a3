"""UE head (models/swin.py ``swin_head``): device ms per frame in the
head program's executions of the device part, from the trace's modules
line.  Nothing is read unless the part ran rounds x UEs of them."""
from chipbench.lib import programs

WRAPS = []
programs.note_profile_dirs()    # before the device part's profiler starts


def read(ctx):
    return programs.device_ms_per_frame(ctx, ("swin_head",),
                                        ctx.traffic.n_ues)
