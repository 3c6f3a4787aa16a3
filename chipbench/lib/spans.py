"""Host spans around the program's layer entry points.

A traced run wraps each entry point that a per-layer metric names (its
reader's ``WRAPS``) with a span: the host clock at entry and exit, and
the span open around it, by which the device trace can say what the host
was doing in each gap.  A wrap spec is ``(span, module, attribute path, block)``; with
``block`` the span ends only once the call's result is ready on the
device.  A target that is not there is reported missing, and the
metrics that need its span read nothing.  Untraced runs install nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import time
import types
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax

@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    parent: Optional[str]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Recorder:
    """Spans on the host clock, kept in memory while ``on``."""

    def __init__(self):
        self.spans: List[Span] = []
        self.on = False
        self._stack: List[str] = []

    def span(self, name: str):
        return _SpanCtx(self, name)


class _SpanCtx:
    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        self.parent = self.rec._stack[-1] if self.rec._stack else None
        self.rec._stack.append(self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.rec._stack.pop()
        if self.rec.on:
            self.rec.spans.append(Span(self.name, self.t0, t1, self.parent))
        return False


def _wrap(fn: Callable, rec: Recorder, name: str, block: bool) -> Callable:
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with rec.span(name):
            out = fn(*args, **kwargs)
            if block:
                jax.block_until_ready(out)
            return out
    return wrapper


def install(specs: Sequence[Tuple[str, str, str, bool]],
            rec: Recorder) -> Tuple[Set[str], Callable[[], None]]:
    """Wrap every target in ``specs``.  Returns (span names whose target
    is missing, a function that undoes the wraps)."""
    missing: Set[str] = set()
    undo: List[Tuple[object, str, object]] = []   # old None: was inherited
    done: Dict[Tuple[str, str], bool] = {}
    for name, module, path, block in specs:
        if (module, path) in done:
            continue
        done[(module, path)] = True
        try:
            owner = importlib.import_module(module)
            parts = path.split(".")
            for i, part in enumerate(parts[:-1]):
                child = getattr(owner, part)
                if isinstance(child, types.ModuleType) and i == 0:
                    # a module the target imports (zlib in the codec):
                    # wrap the target's own reference, not the module
                    proxy = types.ModuleType(child.__name__)
                    proxy.__dict__.update(child.__dict__)
                    undo.append((owner, part, child))
                    setattr(owner, part, proxy)
                    child = proxy
                owner = child
            fn = getattr(owner, parts[-1])
        except (ImportError, AttributeError):
            missing.add(name)
            continue
        undo.append((owner, parts[-1], vars(owner).get(parts[-1])))
        setattr(owner, parts[-1], _wrap(fn, rec, name, block))

    def restore():
        while undo:
            owner, attr, old = undo.pop()
            if old is None:
                delattr(owner, attr)
            else:
                setattr(owner, attr, old)
    return missing, restore
