"""Edge tail (core/splitting.py ``tail_batched``): host ms per frame from
stacking the batch to its detections being ready on the device."""
WRAPS = [("tail", "repro.core.splitting", "SwinSplitPlan.tail_batched",
          True)]


def read(ctx):
    return ctx.per_frame_ms("tail")
