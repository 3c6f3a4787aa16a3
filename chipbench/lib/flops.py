"""Operations and bytes, counted from shapes.

The model's count is a copy of ``stage_flops`` in the program's
``models/swin.py``, so that a change to the program cannot move the
yardstick.  It counts multiply-adds as two operations, in the matrix
products and convolutions only: qkv, q.k and attention.v (over the
``window**2`` real tokens of every window of the padded map), the
output projection, the MLP, the patch merge, and the FPN and detection
head convolutions; not norms, softmax or activations.

The kernel counts are what each kernel call has to do, from its shapes:
the fused window kernel's two matrix products on the real tokens, and the
bytes it has to move once (packed qkv in, attention output out, the bias
table and the window mask); the codec's bytes in and out per element.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from chipbench.lib.model import Shape

F32 = 4


def _block_flops(sh: Shape, H: int, W: int, C: int) -> int:
    k = sh.window
    Hp, Wp = -(-H // k) * k, -(-W // k) * k
    n_win = Hp * Wp // (k * k)
    f = 2 * H * W * C * 3 * C                       # qkv
    f += 2 * n_win * (k * k) * (k * k) * C * 2      # q.k and attn.v
    f += 2 * H * W * C * C                          # proj
    f += 2 * H * W * C * int(sh.mlp_ratio * C) * 2  # mlp
    return f


def stage_flops(sh: Shape) -> Dict[str, int]:
    """Operations per segment: patch_embed, stage0..3 (with the merge that
    follows it), det (FPN + head)."""
    out: Dict[str, int] = {}
    h, w = sh.stage_hw(0)
    out["patch_embed"] = (2 * h * w * sh.embed_dim
                          * sh.patch_size ** 2 * sh.in_chans)
    for s, depth in enumerate(sh.depths):
        H, W = sh.stage_hw(s)
        C = sh.stage_dim(s)
        f = depth * _block_flops(sh, H, W, C)
        if s < sh.n_stages - 1:
            f += 2 * (H // 2) * (W // 2) * 4 * C * 2 * C
        out[f"stage{s}"] = f
    det, fd = 0, sh.fpn_dim
    for s in range(sh.n_stages):
        H, W = sh.stage_hw(s)
        det += 2 * H * W * sh.stage_dim(s) * fd          # lateral
        det += 2 * H * W * fd * fd * 9                   # smooth 3x3
        det += 2 * 2 * H * W * fd * fd * 9               # two head convs
        det += 2 * H * W * fd * (sh.num_classes + 5)     # predictors
    out["det"] = det
    return out


def total_flops(sh: Shape) -> int:
    """One frame through the whole model, whichever side runs which part."""
    return sum(stage_flops(sh).values())


def boundary_shapes(sh: Shape, split: int) -> List[Tuple[int, int, int]]:
    """Per-frame shapes of the tensors that cross the uplink at ``split``:
    stage outputs 1..split, then the merged input of the next stage."""
    out = [sh.stage_hw(s) + (sh.stage_dim(s),) for s in range(split)]
    if split == 0:
        out.append(sh.stage_hw(0) + (sh.stage_dim(0),))
    elif split < sh.n_stages:
        out.append(sh.stage_hw(split) + (sh.stage_dim(split),))
    return out


def boundary_elements(sh: Shape, split: int) -> List[int]:
    return [h * w * c for h, w, c in boundary_shapes(sh, split)]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def window_attn_call(sh: Shape, stage: int, batch: int) -> Tuple[int, int]:
    """(operations, bytes) of one fused window-attention kernel call on
    stage ``stage``'s padded map at ``batch`` frames.  Operations: q.k and
    attention.v of every head of every window, on the window**2 real
    tokens.  Bytes: the packed f32 qkv read once, the f32 output written
    once, the f32 bias table and the int8 mask (both padded to 64 tokens,
    as the kernel takes them)."""
    Hp, Wp = sh.padded_hw(stage)
    C, nh, k = sh.stage_dim(stage), sh.num_heads[stage], sh.window
    hd = C // nh
    n_win = (Hp // k) * (Wp // k)
    t, tp = k * k, -(-k * k // 64) * 64
    flops = batch * n_win * nh * 2 * (2 * t * t * hd)
    nbytes = (batch * Hp * Wp * (3 * C + C) * F32
              + nh * tp * tp * F32 + n_win * tp * tp)
    return flops, nbytes


def window_attn_calls(sh: Shape, first: int, last: int, batch: int
                      ) -> Tuple[int, int, int]:
    """(calls, operations, bytes) of the window kernels of stages
    ``first``..``last - 1`` at ``batch`` frames: one call per block."""
    calls = flops = nbytes = 0
    for s in range(first, last):
        f, b = window_attn_call(sh, s, batch)
        calls += sh.depths[s]
        flops += sh.depths[s] * f
        nbytes += sh.depths[s] * b
    return calls, flops, nbytes


def codec_stream_elements(sh: Shape, split: int, n_frames: int,
                          block: int) -> int:
    """Elements of the packed stream one encode launch covers: every
    boundary tensor of ``n_frames`` frames, each padded to whole blocks."""
    return n_frames * sum(-(-n // block) * block
                          for n in boundary_elements(sh, split))


def codec_encode_bytes(n: int, block: int) -> int:
    """f32 in, int8 out, one f32 scale per block out."""
    return n * F32 + n + (n // block) * F32


def codec_decode_bytes(n: int, block: int) -> int:
    """int8 and scales in, f32 out."""
    return n + (n // block) * F32 + n * F32
