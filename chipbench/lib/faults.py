"""Faults planted under the timed path, which the check has to catch.

Each fault replaces one of the program's entry points while it is
planted, so a run (or ``control.py --faults``) drives the cell as usual
and only the answers change.  ``applicable`` says which faults a cell's
traffic can have: a fault that cannot change its answers (a batch of one
has nothing to misroute) is not one of its faults.
"""
from __future__ import annotations

import contextlib
import importlib
from typing import Callable, Dict, List, Tuple

from chipbench.lib.traffic import Traffic


def _misrouted(orig):
    """Every frame of a batch gets the next frame's detections."""
    def tail_batched(self, payloads, option, pad_to=None):
        outs = orig(self, payloads, option, pad_to)
        return outs[1:] + outs[:1]
    return tail_batched


def _half_batch(orig):
    """Only the first half of the batch is computed; the rest repeat it."""
    def tail_batched(self, payloads, option, pad_to=None):
        half = max(len(payloads) // 2, 1)
        outs = orig(self, payloads[:half], option, pad_to)
        return [outs[i % half] for i in range(len(payloads))]
    return tail_batched


def _altered_payload(orig):
    """The edge decodes every payload with its first tensor zeroed."""
    def decompress_group(self, ps):
        import jax.numpy as jnp
        outs = orig(self, ps)
        return [o | {"feats": [jnp.zeros_like(o["feats"][0])]
                     + list(o["feats"][1:])} for o in outs]
    return decompress_group


def _misrouted_frames(orig):
    """Each UE captures the frame meant for the UE after it."""
    def frame(self, k, u=0):
        return orig(self, k, u + 1)
    return frame


# name -> (module, attribute path, replacement from the original)
FAULTS: Dict[str, Tuple[str, str, Callable]] = {
    "misrouted": ("repro.core.splitting", "SwinSplitPlan.tail_batched",
                  _misrouted),
    "half_batch": ("repro.core.splitting", "SwinSplitPlan.tail_batched",
                   _half_batch),
    "altered_payload": ("repro.core.compression",
                        "ActivationCodec.decompress_group", _altered_payload),
    "misrouted_frames": ("repro.core.pipeline", "FrameSource.frame",
                         _misrouted_frames),
}


def applicable(t: Traffic) -> List[str]:
    """The faults that can change this mix's answers."""
    out = []
    if t.buckets[-1] > 1:
        out += ["misrouted", "half_batch"]
    if t.split is not None:
        out.append("altered_payload")
    if t.n_ues > 1:
        out.append("misrouted_frames")
    return out


@contextlib.contextmanager
def planted(name: str):
    module, path, make = FAULTS[name]
    owner = importlib.import_module(module)
    parts = path.split(".")
    for part in parts[:-1]:
        owner = getattr(owner, part)
    orig = getattr(owner, parts[-1])
    setattr(owner, parts[-1], make(orig))
    try:
        yield
    finally:
        setattr(owner, parts[-1], orig)
