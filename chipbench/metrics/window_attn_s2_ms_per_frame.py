"""Kernels (kernels/window_attention.py, the fused band kernel at stage 2):
the device ms its stage-2 calls took in the device part, per frame the
part completed."""
from chipbench.lib import stage_kernels

WRAPS = []
STAGE = 2


def read(ctx):
    ns = stage_kernels.stage_kernel_ns(ctx, STAGE, __name__)
    frames = ctx.rounds * ctx.traffic.n_ues
    if ns is None or not frames:
        return None
    return ns * 1e-6 / frames
