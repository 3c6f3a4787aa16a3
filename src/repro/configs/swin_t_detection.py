"""Swin object-detection backbones -- the paper's own model (Fig. 2).

Swin-T (arXiv:2103.14030): depths (2,2,6,2), dims (96,192,384,768), heads
(3,6,12,24), window 7, patch 4.  Detection input defaults to 800x544 RGB
uint8 = 1.306 MB, matching the paper's stated 1.312 MB input payload.
Swin-S and Swin-B, from the same paper, are published presets beside it
(``PUBLISHED``, by name); ``reduced()`` is the tests' small model.

The four stage boundaries are the paper's split points S1..S4.  The detection
head (FPN + dense head) always runs on the server side; we implement a
lightweight FPN + FCOS-style dense head instead of the full Mask R-CNN
RPN/RoIAlign stack (noted in DESIGN.md -- the paper never splits the head, so
the split-inference mechanics are unaffected).
"""
from dataclasses import dataclass, field
from typing import Tuple


@dataclass(frozen=True)
class SwinConfig:
    name: str = "swin-t-detection"
    img_h: int = 544
    img_w: int = 800
    in_chans: int = 3
    patch_size: int = 4
    embed_dim: int = 96
    depths: Tuple[int, ...] = (2, 2, 6, 2)
    num_heads: Tuple[int, ...] = (3, 6, 12, 24)
    window: int = 7
    mlp_ratio: float = 4.0
    num_classes: int = 80
    fpn_dim: int = 256
    dtype: str = "float32"
    norm_eps: float = 1e-5
    attn_impl: str = "pallas"   # pallas (fused one-launch, DESIGN.md §13) | xla

    @property
    def n_stages(self) -> int:
        return len(self.depths)

    def stage_dim(self, i: int) -> int:
        return self.embed_dim * (2 ** i)

    def stage_hw(self, i: int) -> Tuple[int, int]:
        """Feature map H, W at the OUTPUT of stage i (post-merge for i>=1)."""
        import math
        h = -(-self.img_h // self.patch_size)
        w = -(-self.img_w // self.patch_size)
        for _ in range(i):
            h = -(-h // 2)
            w = -(-w // 2)
        return h, w


# The published backbones (arXiv:2103.14030 §3.3), each at the paper's
# 800x544 detection input with the FPN + FCOS-style head.
SWIN_T = SwinConfig()   # C 96, depths 2-2-6-2, heads 3-6-12-24, window 7
SWIN_S = SwinConfig(    # C 96, depths 2-2-18-2, heads 3-6-12-24, window 7
    name="swin-s-detection", depths=(2, 2, 18, 2))
SWIN_B = SwinConfig(    # C 128, depths 2-2-18-2, heads 4-8-16-32, window 7
    name="swin-b-detection", embed_dim=128, depths=(2, 2, 18, 2),
    num_heads=(4, 8, 16, 32))
PUBLISHED = {"swin_t": SWIN_T, "swin_s": SWIN_S, "swin_b": SWIN_B}
CONFIG = SWIN_T


def reduced() -> SwinConfig:
    return SwinConfig(
        name="swin-reduced", img_h=56, img_w=56, embed_dim=16,
        depths=(1, 1, 2, 1), num_heads=(1, 2, 2, 4), window=7,
        num_classes=4, fpn_dim=32,
    )
