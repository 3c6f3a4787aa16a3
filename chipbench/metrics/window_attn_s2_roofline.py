"""Kernels (kernels/window_attention.py, the fused band kernel at stage 2,
the deepest stage and the widest band that fits in VMEM only with a raised
limit at Swin-B's 512 channels): the least time the chip needs for the
stage's window-attention calls of the device part's rounds, from their
shapes, as a share of the device time those calls' events took."""
from chipbench.lib import stage_kernels, trace

WRAPS = []
STAGE = 2


def read(ctx):
    ns = stage_kernels.stage_kernel_ns(ctx, STAGE, __name__)
    if ns is None:
        return None
    _, ops, nbytes = stage_kernels.stage_calls(ctx, STAGE)
    return trace.roofline_pct(ops, nbytes, ns * 1e-9,
                              ctx.peaks["flops_per_s"],
                              ctx.peaks["hbm_bytes_per_s"])
