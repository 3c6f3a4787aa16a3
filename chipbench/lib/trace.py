"""From a profiler trace to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
the operations that ran on each TPU (name, start, end, in nanoseconds
from the profiler's start) and the wall-clock instant of that start, by
which the benchmark's own host spans are put on the same clock.  The
rest is plain arithmetic on intervals, kept apart so the tests can check
it on a hand-built trace:

* busy time: the length of the union of a device's operation intervals
  inside the window (nested or overlapping operations count once);
* idle gaps: the holes in that union, each labelled by the innermost
  benchmark span open on the host at the gap's midpoint;
* kernel time: the summed durations of one kernel's operations.

On a TPU an operation's name is its HLO instruction, shapes and all.  A
Pallas kernel is a ``custom-call`` to ``tpu_custom_call`` named after the
jitted function around it, which is a ``lambda`` for every program of the
model, so kernels are told apart by their shapes: the window kernel takes
an int8 mask of (64, 64) tiles; the codec's encode turns an f32 stream of
128-lane rows into int8 rows and per-block scales, and its decode does
the reverse.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
NAME_CHARS = 200           # an operation's name in the breakdown: its HLO
                           # instruction up to here


@dataclasses.dataclass
class Op:
    name: str
    start: float          # ns, trace clock
    end: float
    device: int = 0


@dataclasses.dataclass
class Trace:
    ops: List[Op]                          # device operations
    start_ns: Optional[int]                # wall clock at the trace's 0


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    a, b = window
    out = []
    for s, e in intervals:
        s, e = max(s, a), min(e, b)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Sequence[Op], window: Interval, device: int = 0) -> float:
    merged = union(clip(((o.start, o.end) for o in ops
                         if o.device == device), window))
    return sum(e - s for s, e in merged)


def gaps(ops: Sequence[Op], window: Interval,
         device: int = 0) -> List[Interval]:
    """Holes in the device's busy union inside the window."""
    merged = union(clip(((o.start, o.end) for o in ops
                         if o.device == device), window))
    out, t = [], window[0]
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def host_label(host: Sequence[Op], t: float) -> str:
    """Innermost (latest-starting) benchmark span open at ``t``."""
    best = None
    for h in host:
        if h.start <= t < h.end and (best is None or h.start >= best.start):
            best = h
    return best.name if best is not None else "outside_spans"


_PALLAS = 'custom_call_target="tpu_custom_call"'
_WINDOW_ATTN = re.compile(r"custom-call\(.*\bs8\[\d+,\d+,64,64\]")
_ENCODE = re.compile(r"= \([su]8\[\d+,128\][^ ]* f32\[\d+,1,1\][^ ]* "
                     r"custom-call\(f32\[\d+,128\]")
_DECODE = re.compile(r"= f32\[\d+,128\][^ ]* custom-call\([su]8\[\d+,128\]"
                     r"[^ ]* %[^ ]*, f32\[\d+,1,1\]")


def is_window_attn(name: str) -> bool:
    return _PALLAS in name and bool(_WINDOW_ATTN.search(name))


def is_codec(name: str) -> bool:
    return _PALLAS in name and bool(_ENCODE.search(name)
                                    or _DECODE.search(name))


def kernel_ns(ops: Sequence[Op], match: Callable[[str], bool],
              window: Interval) -> Tuple[float, int]:
    """(summed duration inside the window, number of events) of the
    operations ``match`` picks."""
    total, n = 0.0, 0
    for o in ops:
        if match(o.name):
            d = min(o.end, window[1]) - max(o.start, window[0])
            if d > 0:
                total += d
                n += 1
    return total, n


def top_ops(ops: Sequence[Op], window: Interval, n: int = 10,
            device: int = 0) -> List[Tuple[str, float]]:
    """Device operations by summed seconds inside the window."""
    acc: Dict[str, float] = {}
    for o in ops:
        if o.device != device:
            continue
        d = min(o.end, window[1]) - max(o.start, window[0])
        if d > 0:
            acc[o.name] = acc.get(o.name, 0.0) + d
    ranked = sorted(acc.items(), key=lambda x: -x[1])[:n]
    return [(k[:NAME_CHARS], v * 1e-9) for k, v in ranked]


def labelled_gaps(ops: Sequence[Op], host: Sequence[Op], window: Interval,
                  n: int = 10, device: int = 0) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each named by the host span open at
    its midpoint: [("<span> @<ms into window>", seconds), ...]."""
    gs = sorted(gaps(ops, window, device), key=lambda g: g[0] - g[1])[:n]
    return [(f"{host_label(host, (s + e) / 2)} @{(s - window[0]) * 1e-6:.1f}ms",
             (e - s) * 1e-9) for s, e in gs]


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peak_flops: float, peak_bw: float) -> Optional[float]:
    """Least time the chip could take (the larger of operations over peak
    and bytes over bandwidth) as a percentage of the measured time."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    return 100.0 * max(flops / peak_flops, nbytes / peak_bw) / seconds


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
START_STAT = "profile_start_time"


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: List[Op] = []
    start = None
    for plane in pd.planes:
        for k, v in plane.stats:
            if k == START_STAT:
                start = int(v)
        if plane.name.startswith(DEVICE_PLANE):
            dev = int(plane.name[len(DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append(Op(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, dev))
    return Trace(ops=ops, start_ns=start)
