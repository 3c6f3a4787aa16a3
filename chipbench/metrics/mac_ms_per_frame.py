"""MAC (core/ran.py): host ms per frame in ``RanStream.advance``, the
TTI loop that schedules every uplink over the shared PRB grid."""
WRAPS = [("mac", "repro.core.ran", "RanStream.advance", False)]


def read(ctx):
    return ctx.per_frame_ms("mac")
