"""Swin Transformer backbone + detection head (the paper's model, Fig. 2).

Implements Swin-T (arXiv:2103.14030) in pure JAX: patch embedding, four
stages of shifted-window attention blocks with patch merging between
stages, an FPN neck and an FCOS-style dense detection head.

The module is *stage-structured on purpose*: ``backbone_stages()`` exposes
the paper's split points

    S0 = after patch embedding
    S1..S4 = after stage 1..4

and ``head_apply`` / ``tail_apply`` execute the partitioned forward pass
(core/splitting.py drives them).  The detection neck+head always run on the
server side, exactly as in the paper.

Window attention defaults to ``cfg.attn_impl='pallas'``: the fused
one-launch kernel (kernels/window_attention.py, DESIGN.md §13) on TPUs and
its bitwise-identical pure-jnp mirror everywhere else, so CI exercises the
production dispatch on every run.  ``cfg.attn_impl='xla'`` keeps the plain
rolled/partitioned einsum path as a cross-check.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.swin_t_detection import SwinConfig
from repro.models.layers import layer_norm, init_dense, einsum32

# ---------------------------------------------------------------------------
# relative position bias index (static, numpy)
#
# lru_cached on the int args: these tables are pure functions of the config
# geometry, and uncached they were rebuilt (and re-uploaded to device) on
# every block call of every trace.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def rel_pos_index(window: int) -> np.ndarray:
    coords = np.stack(np.meshgrid(np.arange(window), np.arange(window),
                                  indexing="ij"))          # (2,w,w)
    flat = coords.reshape(2, -1)
    rel = flat[:, :, None] - flat[:, None, :]               # (2,w2,w2)
    rel = rel.transpose(1, 2, 0) + (window - 1)
    return (rel[..., 0] * (2 * window - 1) + rel[..., 1]).astype(np.int32)


@functools.lru_cache(maxsize=None)
def shift_attn_mask(Hp: int, Wp: int, window: int, shift: int) -> np.ndarray:
    """(nW, w2, w2) bool mask: True = may attend (same region)."""
    img = np.zeros((Hp, Wp), np.int32)
    cnt = 0
    slices = (slice(0, -window), slice(-window, -shift), slice(-shift, None))
    for hs in slices:
        for ws in slices:
            img[hs, ws] = cnt
            cnt += 1
    win = img.reshape(Hp // window, window, Wp // window, window)
    win = win.transpose(0, 2, 1, 3).reshape(-1, window * window)
    return (win[:, :, None] == win[:, None, :])


@functools.lru_cache(maxsize=None)
def pad_region_mask(Hp: int, Wp: int, H: int, W: int,
                    window: int) -> np.ndarray:
    """(nW, w2, w2) bool mask isolating the (H:, W:) pad strip: padded
    tokens must not contaminate real ones (pad is its own region)."""
    img = np.zeros((Hp, Wp), np.int32)
    img[H:, :] = 1
    img[:, W:] = 2
    win = img.reshape(Hp // window, window, Wp // window, window)
    win = win.transpose(0, 2, 1, 3).reshape(-1, window * window)
    return (win[:, :, None] == win[:, None, :])


# ---------------------------------------------------------------------------
# init / spec
# ---------------------------------------------------------------------------

def _mlp_init(key, d, hidden, dt):
    k1, k2 = jax.random.split(key)
    return {"w1": init_dense(k1, (d, hidden), dt), "b1": jnp.zeros((hidden,), dt),
            "w2": init_dense(k2, (hidden, d), dt), "b2": jnp.zeros((d,), dt)}


def _block_init(cfg: SwinConfig, key, dim, n_heads):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    w2 = cfg.window * cfg.window
    return {
        "norm1_s": jnp.ones((dim,), dt), "norm1_b": jnp.zeros((dim,), dt),
        "qkv_w": init_dense(ks[0], (dim, 3 * dim), dt),
        "qkv_b": jnp.zeros((3 * dim,), dt),
        "rel_bias": jnp.zeros(((2 * cfg.window - 1) ** 2, n_heads), jnp.float32),
        "proj_w": init_dense(ks[1], (dim, dim), dt),
        "proj_b": jnp.zeros((dim,), dt),
        "norm2_s": jnp.ones((dim,), dt), "norm2_b": jnp.zeros((dim,), dt),
        "mlp": _mlp_init(ks[2], dim, int(dim * cfg.mlp_ratio), dt),
    }


def init(cfg: SwinConfig, key):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 64)
    ki = iter(range(64))
    C = cfg.embed_dim
    params: Dict[str, Any] = {
        "patch_embed": {
            "w": init_dense(ks[next(ki)], (cfg.patch_size, cfg.patch_size,
                                           cfg.in_chans, C), dt,
                            scale=1.0 / math.sqrt(cfg.patch_size ** 2 * cfg.in_chans)),
            "b": jnp.zeros((C,), dt),
            "norm_s": jnp.ones((C,), dt), "norm_b": jnp.zeros((C,), dt),
        },
        "stages": [],
    }
    for si, depth in enumerate(cfg.depths):
        dim = cfg.stage_dim(si)
        stage = {"blocks": [
            _block_init(cfg, ks[next(ki)], dim, cfg.num_heads[si])
            for _ in range(depth)]}
        if si < cfg.n_stages - 1:
            stage["merge"] = {
                "norm_s": jnp.ones((4 * dim,), dt), "norm_b": jnp.zeros((4 * dim,), dt),
                "w": init_dense(ks[next(ki)], (4 * dim, 2 * dim), dt),
            }
        params["stages"].append(stage)
    # FPN + FCOS head (always server-side)
    fd = cfg.fpn_dim
    params["fpn"] = {
        "lateral": [init_dense(ks[next(ki)], (cfg.stage_dim(i), fd), dt)
                    for i in range(cfg.n_stages)],
        "smooth": [init_dense(ks[next(ki)], (3, 3, fd, fd), dt,
                              scale=1.0 / math.sqrt(9 * fd))
                   for _ in range(cfg.n_stages)],
    }
    params["det_head"] = {
        "conv1": init_dense(ks[next(ki)], (3, 3, fd, fd), dt, scale=1.0 / math.sqrt(9 * fd)),
        "conv2": init_dense(ks[next(ki)], (3, 3, fd, fd), dt, scale=1.0 / math.sqrt(9 * fd)),
        "cls_w": init_dense(ks[next(ki)], (fd, cfg.num_classes), dt),
        "cls_b": jnp.full((cfg.num_classes,), -math.log((1 - 0.01) / 0.01), dt),
        "box_w": init_dense(ks[next(ki)], (fd, 4), dt),
        "box_b": jnp.zeros((4,), dt),
        "ctr_w": init_dense(ks[next(ki)], (fd, 1), dt),
        "ctr_b": jnp.zeros((1,), dt),
    }
    return params


def spec(cfg: SwinConfig):
    """Logical sharding spec tree (Swin is small; weights are replicated by
    default, activations batch-sharded -- spec kept for API uniformity)."""
    def like(p):
        return jax.tree.map(lambda a: (None,) * 0, p)
    return like  # placeholder; swin params are replicated in the launch rules


# ---------------------------------------------------------------------------
# forward pieces
# ---------------------------------------------------------------------------

def window_attention(cfg: SwinConfig, p, x, Hp: int, Wp: int, n_heads: int,
                     shift: int, mask: Optional[jnp.ndarray]):
    """x: (B, Hp, Wp, C) pre-normed.  Returns (B, Hp, Wp, C)."""
    B, _, _, C = x.shape
    w = cfg.window
    hd = C // n_heads
    bias = p["rel_bias"][jnp.asarray(rel_pos_index(w))]      # (w2, w2, nh)
    bias = bias.transpose(2, 0, 1)                           # (nh, w2, w2)

    if cfg.attn_impl == "pallas":
        # fused one-launch path (DESIGN.md §13): the kernel owns the roll /
        # partition / un-partition choreography, so qkv and proj run on the
        # image layout and nothing between them touches HBM twice
        from repro.kernels.ops import fused_window_attention
        qkv = einsum32("bhwc,ck->bhwk", x, p["qkv_w"],
                       out_dtype=x.dtype) + p["qkv_b"]
        out = fused_window_attention(qkv, bias, mask, window=w, shift=shift,
                                     n_heads=n_heads)
        return einsum32("bhwc,ck->bhwk", out, p["proj_w"],
                        out_dtype=x.dtype) + p["proj_b"]

    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
    nwh, nww = Hp // w, Wp // w
    xw = x.reshape(B, nwh, w, nww, w, C).transpose(0, 1, 3, 2, 4, 5)
    xw = xw.reshape(B * nwh * nww, w * w, C)                 # (nB, w2, C)

    qkv = einsum32("nsc,ck->nsk", xw, p["qkv_w"], out_dtype=x.dtype) + p["qkv_b"]
    q, k, v = jnp.split(qkv.reshape(-1, w * w, 3, n_heads, hd), 3, axis=2)
    q, k, v = (t[:, :, 0] for t in (q, k, v))                # (nB, w2, nh, hd)

    logits = einsum32("nqhd,nkhd->nhqk", q, k) / math.sqrt(hd)
    logits = logits + bias[None]
    if mask is not None:
        nW = mask.shape[0]
        lg = logits.reshape(B, nW, n_heads, w * w, w * w)
        lg = jnp.where(mask[None, :, None], lg, -1e9)
        logits = lg.reshape(-1, n_heads, w * w, w * w)
    attn = jax.nn.softmax(logits, axis=-1)
    out = einsum32("nhqk,nkhd->nqhd", attn, v, out_dtype=x.dtype)
    out = out.reshape(-1, w * w, C)
    out = einsum32("nsc,ck->nsk", out, p["proj_w"], out_dtype=x.dtype) + p["proj_b"]

    out = out.reshape(B, nwh, nww, w, w, C).transpose(0, 1, 3, 2, 4, 5)
    out = out.reshape(B, Hp, Wp, C)
    if shift:
        out = jnp.roll(out, (shift, shift), axis=(1, 2))
    return out


def swin_block(cfg: SwinConfig, p, x, H: int, W: int, n_heads: int, shift: int):
    """x: (B, H, W, C) unpadded feature map."""
    B, _, _, C = x.shape
    w = cfg.window
    Hp, Wp = -(-H // w) * w, -(-W // w) * w
    h = layer_norm(x, p["norm1_s"], p["norm1_b"], cfg.norm_eps)
    if (Hp, Wp) != (H, W):
        h = jnp.pad(h, ((0, 0), (0, Hp - H), (0, Wp - W), (0, 0)))
    mask = None
    if shift:
        mask = jnp.asarray(shift_attn_mask(Hp, Wp, w, shift))
    elif (Hp, Wp) != (H, W):
        mask = jnp.asarray(pad_region_mask(Hp, Wp, H, W, w))
    h = window_attention(cfg, p, h, Hp, Wp, n_heads, shift, mask)
    h = h[:, :H, :W]
    x = x + h
    h2 = layer_norm(x, p["norm2_s"], p["norm2_b"], cfg.norm_eps)
    m = p["mlp"]
    h2 = jax.nn.gelu(einsum32("bhwc,ck->bhwk", h2, m["w1"]) + m["b1"]).astype(x.dtype)
    h2 = einsum32("bhwk,kc->bhwc", h2, m["w2"], out_dtype=x.dtype) + m["b2"]
    return x + h2


def patch_embed(cfg: SwinConfig, p, img):
    """img: (B, H, W, 3) float in [0,1].  Returns (B, H/4, W/4, C)."""
    x = jax.lax.conv_general_dilated(
        img.astype(jnp.dtype(cfg.dtype)),
        p["w"], window_strides=(cfg.patch_size, cfg.patch_size),
        padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x = x + p["b"]
    return layer_norm(x, p["norm_s"], p["norm_b"], cfg.norm_eps)


def patch_merge(cfg: SwinConfig, p, x):
    """(B,H,W,C) -> (B,ceil(H/2),ceil(W/2),2C)."""
    B, H, W, C = x.shape
    if H % 2 or W % 2:
        x = jnp.pad(x, ((0, 0), (0, H % 2), (0, W % 2), (0, 0)))
        H, W = x.shape[1], x.shape[2]
    x = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, H // 2, W // 2, 4 * C)
    x = layer_norm(x, p["norm_s"], p["norm_b"], cfg.norm_eps)
    return einsum32("bhwc,ck->bhwk", x, p["w"], out_dtype=x.dtype)


def stage_apply(cfg: SwinConfig, params, x, stage: int):
    """Run stage ``stage`` (blocks + trailing merge).  Returns
    (pre_merge_feature, post_merge_x)."""
    sp = params["stages"][stage]
    H, W = x.shape[1], x.shape[2]
    nh = cfg.num_heads[stage]
    for bi, bp in enumerate(sp["blocks"]):
        shift = 0 if bi % 2 == 0 else cfg.window // 2
        x = swin_block(cfg, bp, x, H, W, nh, shift)
    feat = x
    if "merge" in sp:
        x = patch_merge(cfg, sp["merge"], x)
    return feat, x


# ---------------------------------------------------------------------------
# split-structured forward (the paper's head/tail partition)
# ---------------------------------------------------------------------------

N_SPLITS = 5   # split l in {0..4}: 0 = after patch embed, k = after stage k
               # plus the two degenerate modes UE-only / server-only handled
               # by core/splitting.py


def head_apply(cfg: SwinConfig, params, img, split: int, *,
               ship_merged: bool = True):
    """Run the UE part: patch-embed + stages 1..split.

    Returns the boundary payload: the features the server still needs.
    Stage outputs feed both the next stage and the FPN, so a split after
    stage k ships stage outputs 1..k plus the merged running tensor.

    ship_merged=False is the beyond-paper payload optimization: the merged
    tensor is NOT shipped; the server recomputes the (cheap) patch-merge
    from the last stage output, cutting the deepest boundary tensor from
    the payload (payload sizes per split: benchmarks/bench_compression.py
    -> results/bench_compression.json).
    """
    x = patch_embed(cfg, params["patch_embed"], img)
    feats: List[jnp.ndarray] = []
    for s in range(split):
        f, x = stage_apply(cfg, params, x, s)
        feats.append(f)
    payload = {"feats": feats}
    if split == 0:
        payload["x"] = x                       # patch-embed output is the payload
    elif split < cfg.n_stages and ship_merged:
        payload["x"] = x
    return payload


def tail_apply(cfg: SwinConfig, params, boundary, split: int):
    """Run the server part: stages split+1..4, FPN, detection head."""
    feats = list(boundary["feats"])
    if "x" in boundary:
        x = boundary["x"]
    elif split < cfg.n_stages:                 # recompute merge server-side
        x = patch_merge(cfg, params["stages"][split - 1]["merge"], feats[-1])
    else:
        x = None
    for s in range(split, cfg.n_stages):
        f, x = stage_apply(cfg, params, x, s)
        feats.append(f)
    return detection_head(cfg, params, feats)


def forward_full(cfg: SwinConfig, params, img):
    return tail_apply(cfg, params, head_apply(cfg, params, img, 0), 0)


# -- batched tail entry (edge-server micro-batching) -------------------------

_TAIL_JIT: Dict[Tuple[SwinConfig, int], Any] = {}


def tail_apply_jit(cfg: SwinConfig, split: int):
    """Cached jitted ``tail_apply`` for one (config, split), the program
    ``swin_tail``.  The edge server's batcher calls this once per
    micro-batch; padding occupancies to bucketed batch sizes keeps the
    trace cache small."""
    key = (cfg, split)
    if key not in _TAIL_JIT:
        def swin_tail(params, boundary):
            return tail_apply(cfg, params, boundary, split)
        _TAIL_JIT[key] = jax.jit(swin_tail)
    return _TAIL_JIT[key]


# -- per-frame head entries (UE side) -----------------------------------------

_HEAD_JIT: Dict[Tuple[SwinConfig, int, bool], Any] = {}


def head_apply_jit(cfg: SwinConfig, split: int, ship_merged: bool = True):
    """Cached jitted ``head_apply`` for one (config, split, ship_merged),
    the program ``swin_head``.  The UE runs this once per frame; without
    the cache every frame paid a full retrace (SwinConfig is
    frozen/hashable, so the key is cheap)."""
    key = (cfg, split, ship_merged)
    if key not in _HEAD_JIT:
        def swin_head(params, img):
            return head_apply(cfg, params, img, split,
                              ship_merged=ship_merged)
        _HEAD_JIT[key] = jax.jit(swin_head)
    return _HEAD_JIT[key]


_FULL_JIT: Dict[SwinConfig, Any] = {}


def forward_full_jit(cfg: SwinConfig):
    """Cached jitted whole-model forward, the program ``swin_full``: the
    UE_ONLY degenerate split, and the edge's program for SERVER_ONLY."""
    if cfg not in _FULL_JIT:
        def swin_full(params, img):
            return forward_full(cfg, params, img)
        _FULL_JIT[cfg] = jax.jit(swin_full)
    return _FULL_JIT[cfg]


# ---------------------------------------------------------------------------
# FPN + FCOS-style head
# ---------------------------------------------------------------------------

def _conv3(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def detection_head(cfg: SwinConfig, params, feats):
    """feats: per-stage features (B, H_i, W_i, C_i).  Returns per-level dicts
    of cls/box/centerness maps (FCOS-style dense predictions)."""
    fpn = params["fpn"]
    lat = [einsum32("bhwc,ck->bhwk", f, w, out_dtype=f.dtype)
           for f, w in zip(feats, fpn["lateral"])]
    # top-down pathway
    outs = [None] * len(lat)
    prev = lat[-1]
    outs[-1] = prev
    for i in range(len(lat) - 2, -1, -1):
        up = jnp.repeat(jnp.repeat(prev, 2, axis=1), 2, axis=2)
        up = up[:, :lat[i].shape[1], :lat[i].shape[2]]
        prev = lat[i] + up
        outs[i] = prev
    outs = [_conv3(o, w) for o, w in zip(outs, fpn["smooth"])]

    head = params["det_head"]
    levels = []
    for o in outs:
        h = jax.nn.relu(_conv3(o, head["conv1"]))
        h = jax.nn.relu(_conv3(h, head["conv2"]))
        levels.append({
            "cls": einsum32("bhwc,ck->bhwk", h, head["cls_w"]) + head["cls_b"].astype(jnp.float32),
            "box": jax.nn.relu(einsum32("bhwc,ck->bhwk", h, head["box_w"]) + head["box_b"].astype(jnp.float32)),
            "ctr": einsum32("bhwc,ck->bhwk", h, head["ctr_w"]) + head["ctr_b"].astype(jnp.float32),
        })
    return levels


def detection_loss(cfg: SwinConfig, levels, targets):
    """Simple dense detection loss (focal-BCE cls + L1 box on positives).

    targets: dict(cls=(B,H,W) int labels per level list, box=(B,H,W,4),
    pos=(B,H,W) bool).  Used by the training example; the paper itself runs
    inference-only.
    """
    total = jnp.zeros(())
    for lv, tg in zip(levels, targets):
        cls_t = jax.nn.one_hot(tg["cls"], cfg.num_classes)
        pc = jax.nn.sigmoid(lv["cls"])
        focal = -(cls_t * (1 - pc) ** 2 * jnp.log(pc + 1e-8)
                  + (1 - cls_t) * pc ** 2 * jnp.log(1 - pc + 1e-8))
        total = total + focal.mean()
        pos = tg["pos"][..., None].astype(jnp.float32)
        l1 = jnp.abs(lv["box"] - tg["box"]) * pos
        total = total + l1.sum() / jnp.maximum(pos.sum() * 4, 1.0)
    return total


# ---------------------------------------------------------------------------
# analytic FLOPs (drives the energy model + split controller)
# ---------------------------------------------------------------------------

def _block_flops(cfg: SwinConfig, H: int, W: int, C: int) -> int:
    w = cfg.window
    Hp, Wp = -(-H // w) * w, -(-W // w) * w
    n = Hp * Wp
    nw = n // (w * w)
    f = 0
    f += 2 * H * W * C * 3 * C                 # qkv
    f += 2 * nw * (w * w) * (w * w) * C * 2    # qk^T and pv
    f += 2 * H * W * C * C                     # proj
    f += 2 * H * W * C * int(cfg.mlp_ratio * C) * 2   # mlp
    return f


def stage_flops(cfg: SwinConfig) -> Dict[str, int]:
    """FLOPs per pipeline segment: patch_embed, stage0..3 (incl. merge), det."""
    out: Dict[str, int] = {}
    h, w = cfg.stage_hw(0)
    out["patch_embed"] = 2 * h * w * cfg.embed_dim * (cfg.patch_size ** 2 * cfg.in_chans)
    for s, depth in enumerate(cfg.depths):
        H, W = cfg.stage_hw(s)
        C = cfg.stage_dim(s)
        f = depth * _block_flops(cfg, H, W, C)
        if s < cfg.n_stages - 1:
            f += 2 * (H // 2) * (W // 2) * 4 * C * 2 * C   # patch merge
        out[f"stage{s}"] = f
    det = 0
    fd = cfg.fpn_dim
    for s in range(cfg.n_stages):
        H, W = cfg.stage_hw(s)
        C = cfg.stage_dim(s)
        det += 2 * H * W * C * fd                      # lateral
        det += 2 * H * W * fd * fd * 9                 # smooth 3x3
        det += 2 * 2 * H * W * fd * fd * 9             # two head convs
        det += 2 * H * W * fd * (cfg.num_classes + 5)  # predictors
    out["det"] = det
    return out


def total_flops(cfg: SwinConfig) -> int:
    return sum(stage_flops(cfg).values())


def head_flops(cfg: SwinConfig, split: int) -> int:
    """UE-side FLOPs for split l (0 = after patch embed)."""
    sf = stage_flops(cfg)
    f = sf["patch_embed"]
    for s in range(split):
        f += sf[f"stage{s}"]
    return f


def tail_flops(cfg: SwinConfig, split: int) -> int:
    return total_flops(cfg) - head_flops(cfg, split)


# ---------------------------------------------------------------------------
# activation payload accounting (paper Fig. 3 x-axis)
# ---------------------------------------------------------------------------

def boundary_shapes(cfg: SwinConfig, split: int, *,
                    ship_merged: bool = True) -> List[Tuple[int, ...]]:
    """Shapes (no batch dim) of every tensor shipped at split l."""
    shapes = []
    for s in range(split):                      # FPN needs stage outputs 1..l
        h, w = cfg.stage_hw(s)
        shapes.append((h, w, cfg.stage_dim(s)))
    if split == 0:
        h, w = cfg.stage_hw(0)
        shapes.append((h, w, cfg.stage_dim(0)))
    elif split < cfg.n_stages and ship_merged:
        h, w = cfg.stage_hw(split)
        shapes.append((h, w, cfg.stage_dim(split)))
    return shapes


def boundary_bytes(cfg: SwinConfig, split: int, dtype_bytes: int = 4, *,
                   ship_merged: bool = True) -> int:
    return sum(int(np.prod(s)) * dtype_bytes
               for s in boundary_shapes(cfg, split, ship_merged=ship_merged))
