"""The program's own instrumentation, for the metrics that read it.

Two sources that the harness's ``TraceContext`` does not carry:

* the program's host spans (``repro.core.telemetry.host_span``): loading
  a reader of them, which happens in a ``--trace 1`` run only, attaches a
  program ``HostRecorder`` for the rest of the run.  A reader keeps the
  spans that lie inside the span part, whose bounds are those of the
  benchmark's own ``loop`` spans there (``ctx.spans``).
* the device trace's per-program events: each TPU plane's "XLA Modules"
  line holds one event per execution of a compiled program, named after
  the jitted function (``jit_swin_head``).  The harness keeps only the
  operations of the trace, so loading a reader of them wraps
  ``jax.profiler.start_trace`` to note the directory each profiler
  session writes, and the reader reads the modules line from the same
  file.

A program without ``host_span`` or without named programs gives nothing
here, and the metrics read nothing.  Untraced runs load no reader and so
attach nothing.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from chipbench.lib import trace as trace_mod

MODULES_LINE = "XLA Modules"

# -- program host spans ------------------------------------------------------

_RECORDER = None
_ATTACHED = None        # the entered ``recording``, kept so it stays open


def recorder():
    """The program's ``HostRecorder``, attached on first use for the rest
    of the run; None when the program has no host spans."""
    global _RECORDER, _ATTACHED
    if _RECORDER is None:
        try:
            from repro.core import telemetry
            rec = telemetry.HostRecorder()
            attached = telemetry.recording(rec)
        except (ImportError, AttributeError):
            return None
        attached.__enter__()
        _RECORDER, _ATTACHED = rec, attached
    return _RECORDER


def span_part(ctx) -> Optional[Tuple[float, float]]:
    """Host-clock bounds of the span part: its first ``loop`` span's start
    to its last one's end.  None when the part read nothing."""
    loops = [s for s in ctx.spans if s.name == "loop"]
    if not loops or not ctx.frames:
        return None
    return min(s.t0 for s in loops), max(s.t1 for s in loops)


def part_spans(ctx):
    """The program's spans inside the span part, or None."""
    rec, part = recorder(), span_part(ctx)
    if rec is None or part is None:
        return None
    a, b = part
    return [s for s in rec.spans if s.t0 >= a and s.t1 <= b]


def outermost(spans: Sequence, match) -> List:
    """The spans ``match`` picks that no other picked span on the same
    thread contains: nested copies count once."""
    picked = [s for s in spans if match(s.name)]
    by_thread: Dict[int, List] = {}
    for s in sorted(picked, key=lambda s: (s.t0, -s.t1)):
        by_thread.setdefault(s.thread, []).append(s)
    out = []
    for ss in by_thread.values():
        end = None
        for s in ss:
            if end is not None and s.t1 <= end:
                continue                     # inside the last kept span
            out.append(s)
            end = s.t1
    return out


def span_ms_per_frame(ctx, match) -> Optional[float]:
    """Summed seconds of the outermost program spans ``match`` picks in the
    span part, in ms per frame completed there."""
    spans = part_spans(ctx)
    if spans is None:
        return None
    return 1e3 * sum(s.seconds for s in outermost(spans, match)) / ctx.frames


# -- per-program device events -----------------------------------------------

_DIRS: List[str] = []


def note_profile_dirs():
    """From now on, note the directory of every profiler session the run
    starts (``jax.profiler.start_trace`` is wrapped once)."""
    start_trace = jax.profiler.start_trace
    if getattr(start_trace, "notes_dir", False):
        return

    @functools.wraps(start_trace)
    def wrapper(log_dir, *args, **kwargs):
        _DIRS.append(os.fspath(log_dir))
        return start_trace(log_dir, *args, **kwargs)
    wrapper.notes_dir = True
    jax.profiler.start_trace = wrapper

_PROGRAM = re.compile(r"^(?:jit_)?([A-Za-z0-9_]+?)(?:\.\d+)?(?:\(\d+\))?$")


def program_name(event_name: str) -> str:
    """``jit_swin_head(123)`` -> ``swin_head``: the jitted function's name
    from a modules-line event."""
    m = _PROGRAM.match(event_name.strip())
    return m.group(1) if m else event_name


def load_modules(log_dir: str) -> List[trace_mod.Op]:
    """Every program execution on a TPU plane of the profile in
    ``log_dir``: (program name, start, end, device) on the trace's clock."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return []
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith(trace_mod.DEVICE_PLANE):
            continue
        dev = int(plane.name[len(trace_mod.DEVICE_PLANE):].split()[0])
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out.extend(trace_mod.Op(program_name(e.name), e.start_ns,
                                        e.start_ns + e.duration_ns, dev)
                           for e in line.events)
    return out


def device_modules(ctx) -> Optional[List[trace_mod.Op]]:
    """The device part's program executions, or None when the part read
    nothing."""
    if ctx.trace is None or ctx.window_ns is None or not _DIRS:
        return None
    return load_modules(_DIRS[-1])


def program_device_ms(modules: Sequence[trace_mod.Op], names: Sequence[str],
                      window: Tuple[float, float], calls: int,
                      frames: int) -> Optional[float]:
    """Device ms per frame of the executions of the programs ``names``
    inside ``window``; nothing unless there were exactly ``calls``."""
    ns, n = trace_mod.kernel_ns(modules, lambda name: name in names, window)
    if n != calls or not frames:
        print(f"chipbench.programs: {n} executions of {'/'.join(names)} in "
              f"the device part, {calls} expected: the count does not "
              "hold, nothing read", file=sys.stderr)
        return None
    return 1e-6 * ns / frames


def device_ms_per_frame(ctx, names: Sequence[str],
                        per_round: int) -> Optional[float]:
    modules = device_modules(ctx)
    if modules is None:
        return None
    return program_device_ms(modules, names, ctx.window_ns,
                             per_round * ctx.rounds,
                             ctx.traffic.n_ues * ctx.rounds)
