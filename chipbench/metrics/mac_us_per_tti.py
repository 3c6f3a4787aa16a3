"""MAC (core/ran.py ``RanStream.advance``): host us per TTI in the span
part, the summed seconds of the program's ``mac.advance`` spans over the
TTIs they count (``ttis``): a faster TTI, not fewer TTIs."""
from chipbench.lib import programs

WRAPS = []
programs.recorder()             # attached now: spans are kept from set-up on


def read(ctx):
    spans = programs.part_spans(ctx)
    if spans is None:
        return None
    mac = [s for s in spans if s.name == "mac.advance"]
    ttis = sum(s.attrs.get("ttis", 0) for s in mac)
    if not ttis:
        return None
    return 1e6 * sum(s.seconds for s in mac) / ttis
