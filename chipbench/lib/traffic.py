"""Traffic generation: the frames each camera sends, and its radio levels.

``SyntheticVideo`` and ``interference_walk`` are copies of the program's
``data/video.py`` and of ``cell_interference_traces`` in ``core/cell.py``,
kept here so that a change to the program cannot change what the
benchmark sends.  ``Traffic`` reads one mix's data file.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

INTERFERENCE_LEVELS = (-40.0, -30.0, -20.0, -10.0, -5.0)   # dB, the paper's


def _smooth_background(rng, h: int, w: int) -> np.ndarray:
    """Low-frequency background: bilinear upsampling of 8x8 coarse noise."""
    coarse = rng.uniform(0.15, 0.7, (8, 8, 3))
    ys, xs = np.linspace(0, 7, h), np.linspace(0, 7, w)
    y0, x0 = np.floor(ys).astype(int), np.floor(xs).astype(int)
    y1, x1 = np.minimum(y0 + 1, 7), np.minimum(x0 + 1, 7)
    wy, wx = (ys - y0)[:, None, None], (xs - x0)[None, :, None]
    return ((1 - wy) * (1 - wx) * coarse[y0][:, x0]
            + (1 - wy) * wx * coarse[y0][:, x1]
            + wy * (1 - wx) * coarse[y1][:, x0]
            + wy * wx * coarse[y1][:, x1])


class SyntheticVideo:
    """A camera's clip: smooth background, moving striped boxes, sensor
    noise.  Deterministic in ``seed``."""

    def __init__(self, h: int, w: int, seed: int, n_objects: int = 4,
                 noise: float = 0.01):
        self.h, self.w, self.seed, self.noise = h, w, seed, noise
        rng = np.random.default_rng(seed)
        self._bg = _smooth_background(rng, h, w)
        self._obj = [{
            "xy": rng.uniform([0.1 * w, 0.1 * h], [0.8 * w, 0.8 * h]),
            "vel": rng.uniform(-6, 6, 2),
            "size": rng.uniform([40, 30], [160, 120]),
            "color": rng.uniform(0.2, 1.0, 3),
        } for _ in range(n_objects)]

    def frame(self, t: int) -> np.ndarray:
        """(h, w, 3) float32 in [0, 1]."""
        img = self._bg.copy()
        rng = np.random.default_rng([self.seed, t])
        for ob in self._obj:
            x, y = ob["xy"] + ob["vel"] * t
            x = float(np.abs((x % (2 * self.w)) - self.w) % self.w)
            y = float(np.abs((y % (2 * self.h)) - self.h) % self.h)
            sw, sh = ob["size"]
            x0, y0 = int(max(x - sw / 2, 0)), int(max(y - sh / 2, 0))
            x1, y1 = int(min(x + sw / 2, self.w)), int(min(y + sh / 2, self.h))
            if x1 <= x0 or y1 <= y0:
                continue
            stripe = 0.85 + 0.15 * np.sin(np.arange(y0, y1)[:, None] / 6.0)
            img[y0:y1, x0:x1] = ob["color"] * stripe[..., None]
        img = img + rng.normal(0, self.noise, img.shape)
        return np.clip(img, 0, 1).astype(np.float32)


def interference_walk(n_rounds: int, n_ues: int, seed: int,
                      levels: Sequence[float] = INTERFERENCE_LEVELS,
                      p_move: float = 0.2) -> np.ndarray:
    """(n_rounds, n_ues) jammer levels: per UE a sticky random walk over
    ``levels``."""
    rng = np.random.default_rng(seed)
    levels = np.asarray(levels, float)
    idx = rng.integers(0, len(levels), size=n_ues)
    out = np.empty((n_rounds, n_ues))
    for t in range(n_rounds):
        move = rng.random(n_ues) < p_move
        idx = np.clip(idx + np.where(move, rng.integers(-1, 2, size=n_ues),
                                     0), 0, len(levels) - 1)
        out[t] = levels[idx]
    return out


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One traffic mix, as its data file states it.

    Every round is one capture by each of ``n_ues`` cameras at the same
    instant, all on ``option``; the edge serves them as full batches of
    the largest bucket.  The frames come from a pool of
    ``pool_frames`` frames of one clip; the levels of a round are the
    cell's level set (row 0 of the interference walk drawn with
    ``levels_seed``), dealt to the UEs in an order drawn from the run's
    seed.  So every seed sends the same sizes and levels, in another
    order.  A traced run reads the layer spans over ``trace_seconds`` of
    rounds and the device trace over as many again.  A file leaves out
    a field that keeps its default here."""
    n_ues: int
    option: str
    buckets: Tuple[int, ...]
    mac: Optional[Dict[str, Any]]
    trace_seconds: float
    pool_frames: int = 16
    clip_seed: int = 0
    levels_seed: int = 0

    @classmethod
    def from_file(cls, d: Dict[str, Any]) -> "Traffic":
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - names
        if unknown:
            raise ValueError(f"unknown traffic fields {sorted(unknown)}")
        t = cls(**dict(d, buckets=tuple(d["buckets"])))
        if t.n_ues % t.buckets[-1]:
            raise ValueError("the UE count must be a multiple of the largest "
                             "bucket, so that each round is whole batches")
        if t.pool_frames < t.n_ues:
            raise ValueError("the pool must hold a frame for every UE, so "
                             "that the frames of a round all differ")
        return t

    @property
    def split(self) -> Optional[int]:
        """Boundary stage, or None when the frame goes raw to the edge."""
        if self.option == "server_only":
            return None
        if not self.option.startswith("split"):
            raise ValueError(f"unsupported option {self.option!r}")
        return int(self.option[len("split"):])

    def pool(self, h: int, w: int) -> List[np.ndarray]:
        video = SyntheticVideo(h, w, self.clip_seed)
        return [video.frame(t)[None] for t in range(self.pool_frames)]

    def level_set(self) -> np.ndarray:
        return interference_walk(1, self.n_ues, self.levels_seed)[0]


class Schedule:
    """Seeded order of the pool frames and of the levels, round by round."""

    def __init__(self, traffic: Traffic, seed: int):
        self.t = traffic
        self.rng = np.random.default_rng([seed % 2 ** 32, seed // 2 ** 32])
        self.levels = traffic.level_set()
        self.order = self.rng.permutation(traffic.pool_frames)

    def round(self, r: int) -> Tuple[List[int], np.ndarray, int]:
        """(pool index per UE, level per UE, simulator seed) of round r.
        UE u sends the pool frame u places after the round's start."""
        p = self.t.pool_frames
        frames = [int(self.order[(r + u) % p]) for u in range(self.t.n_ues)]
        return (frames, self.rng.permutation(self.levels),
                int(self.rng.integers(2 ** 31)))
