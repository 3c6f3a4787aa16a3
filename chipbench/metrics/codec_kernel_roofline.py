"""Kernels (kernels/codec.py): the least time the chip needs for the
encode and decode launches of the device part's rounds, bytes-bound (each
element read once and written once, from the packed stream's size), as a
share of the device time the kernels' events took."""
import sys

from chipbench.lib import flops, trace

WRAPS = []


def read(ctx):
    t = ctx.traffic
    if ctx.trace is None or ctx.window_ns is None or t.split is None:
        return None
    n = flops.codec_stream_elements(ctx.shape, t.split, t.n_ues,
                                    ctx.quant_block)
    nbytes = ctx.rounds * (flops.codec_encode_bytes(n, ctx.quant_block)
                           + flops.codec_decode_bytes(n, ctx.quant_block))
    ns, k = trace.kernel_ns(ctx.trace.ops, trace.is_codec, ctx.window_ns)
    calls = 2 * ctx.rounds
    if k != calls:
        print(f"{__name__}: {k} kernel events in the device part, {calls} "
              "calls expected: the count does not hold, nothing read",
              file=sys.stderr)
        return None
    return trace.roofline_pct(0, nbytes, ns * 1e-9, ctx.peaks["flops_per_s"],
                              ctx.peaks["hbm_bytes_per_s"])
