"""Codec, host part: ms per frame in ``zlib.compress`` and
``zlib.decompress`` as core/compression.py calls them."""
_M = "repro.core.compression"
WRAPS = [("zlib", _M, "zlib.compress", False),
         ("zlib", _M, "zlib.decompress", False)]


def read(ctx):
    return ctx.per_frame_ms("zlib")
