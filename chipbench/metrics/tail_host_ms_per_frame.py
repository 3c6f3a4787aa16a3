"""Edge tail, host part (core/splitting.py ``tail_batched``): ms per frame
in the program's ``tail`` spans of the span part (stacking the batch,
with any frame upload, dispatching the tail program, slicing its output
back into frames).  The spans do not wait for the device."""
from chipbench.lib import programs

WRAPS = []
programs.recorder()             # attached now: spans are kept from set-up on


def read(ctx):
    return programs.span_ms_per_frame(ctx, lambda name: name == "tail")
