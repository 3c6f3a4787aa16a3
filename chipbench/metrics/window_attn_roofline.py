"""Kernels (kernels/window_attention.py, the fused band kernel): the least
time the chip needs for the window-attention calls of the device part's
rounds, from their shapes, as a share of the device time the kernel's
events took."""
import sys

from chipbench.lib import flops, trace

WRAPS = []


def read(ctx):
    if ctx.trace is None or ctx.window_ns is None:
        return None
    t, sh = ctx.traffic, ctx.shape
    split, batch = t.split or 0, t.buckets[-1]
    head = flops.window_attn_calls(sh, 0, split, 1)        # per UE
    tail = flops.window_attn_calls(sh, split, sh.n_stages, batch)
    per_round = [t.n_ues * h + t.n_ues // batch * x
                 for h, x in zip(head, tail)]
    calls, ops, nbytes = (ctx.rounds * x for x in per_round)
    ns, n = trace.kernel_ns(ctx.trace.ops, trace.is_window_attn,
                            ctx.window_ns)
    if n != calls:
        print(f"{__name__}: {n} kernel events in the device part, {calls} "
              "calls expected: the count does not hold, nothing read",
              file=sys.stderr)
        return None
    return trace.roofline_pct(ops, nbytes, ns * 1e-9,
                              ctx.peaks["flops_per_s"],
                              ctx.peaks["hbm_bytes_per_s"])
