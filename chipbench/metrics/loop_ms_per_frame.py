"""Frame loop (core/timeline.py, core/cell.py): host ms per frame spent
inside the benchmark's ``run_stream`` call but outside the layer spans
under it (head, codec, MAC, tail): the event engine's own self time."""
WRAPS = []


def read(ctx):
    if ctx.missing or not ctx.frames:
        return None
    loop = sum(s.seconds for s in ctx.spans if s.name == "loop")
    under = sum(s.seconds for s in ctx.spans if s.parent == "loop")
    return 1e3 * (loop - under) / ctx.frames
