"""Codec (core/compression.py ``ActivationCodec``): host ms per frame in
encode and decode, group or single: the quant launch, the copies between
host and device, zlib, and the dequant launch until its output is ready."""
_M = "repro.core.compression"
WRAPS = [("codec", _M, "ActivationCodec.compress_group", False),
         ("codec", _M, "ActivationCodec.decompress_group", True),
         ("codec", _M, "ActivationCodec.compress", False),
         ("codec", _M, "ActivationCodec.decompress", True)]


def read(ctx):
    return ctx.per_frame_ms("codec")
