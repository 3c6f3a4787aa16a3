"""One stage's window-attention kernel calls in a device trace.

The program runs each Swin stage's blocks under the name scope
``swin.stage<s>`` (``models/swin.py`` ``stage_apply``), and the TPU
compiler names a Pallas kernel's instruction after the innermost scope
around it: the window kernel of stage 2 is ``%swin.stage2.<n> = ...
custom-call(...)``.  A program without the scope names the kernel after
its jitted function, so no event matches here and a metric that reads
them reads nothing.
"""
from __future__ import annotations

import re
import sys
from typing import Tuple

from chipbench.lib import flops, trace


def is_stage_kernel(name: str, stage: int) -> bool:
    return (trace.is_window_attn(name)
            and re.match(rf"%?swin\.stage{stage}\.\d+ ", name) is not None)


def stage_calls(ctx, stage: int) -> Tuple[int, int, int]:
    """(calls, operations, bytes) of stage ``stage``'s window kernels in
    the device part's rounds: one call per block, on the UE's side at one
    frame a call where the split lies after the stage, else on the edge's
    at the cell's largest bucket."""
    t, sh = ctx.traffic, ctx.shape
    if stage < (t.split or 0):
        launches, batch = t.n_ues, 1
    else:
        launches, batch = t.n_ues // t.buckets[-1], t.buckets[-1]
    calls, ops, nbytes = flops.window_attn_calls(sh, stage, stage + 1, batch)
    n = ctx.rounds * launches
    return n * calls, n * ops, n * nbytes


def stage_kernel_ns(ctx, stage: int, reader: str):
    """Summed device ns of the stage's window-kernel events in the device
    part, or None where the trace is missing or the number of events is
    not the number of calls (the message goes to standard error)."""
    if ctx.trace is None or ctx.window_ns is None:
        return None
    calls = stage_calls(ctx, stage)[0]
    ns, n = trace.kernel_ns(ctx.trace.ops,
                            lambda name: is_stage_kernel(name, stage),
                            ctx.window_ns)
    if n != calls:
        print(f"{reader}: {n} stage-{stage} kernel events in the device "
              f"part, {calls} calls expected: the count does not hold, "
              "nothing read", file=sys.stderr)
        return None
    return ns
