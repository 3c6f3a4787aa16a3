"""Host-device copies: ms per frame in the program's outermost ``copy.*``
spans of the span part: the frame upload (``copy.frame_h2d``, at the head
or in the tail's stack), the codec's stream download (``copy.codec_d2h``)
and upload at decode (``copy.codec_h2d``).  Host time to start each copy;
a download includes the transfer itself."""
from chipbench.lib import programs

WRAPS = []
programs.recorder()             # attached now: spans are kept from set-up on


def read(ctx):
    return programs.span_ms_per_frame(
        ctx, lambda name: name.startswith("copy."))
