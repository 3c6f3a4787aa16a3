"""UE head (core/splitting.py ``SwinSplitPlan.head`` and the head program
it launches): host ms per frame from the call to its payload being ready
on the device."""
WRAPS = [("head", "repro.core.splitting", "SwinSplitPlan.head", True)]


def read(ctx):
    return ctx.per_frame_ms("head")
