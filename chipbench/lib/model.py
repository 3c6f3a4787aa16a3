"""The benchmark's own view of the model: shapes, weights, reference.

Nothing here imports the program.  ``Shape`` holds the sizes a
configuration file states.  ``init_params`` makes the weights from the
seed, on the device, in one jitted call, in the pytree layout the program
takes (a layout is an interface, so both sides follow it: the leaf names,
q|k|v packed head-major in ``qkv_w``, the 2x2 patch-merge neighbours
concatenated (dy, dx) row-major).  ``reference_forward`` is the plain
forward pass the served detections are compared with: straightforward
``jax.numpy``, window attention by partition and roll, no kernels, no
batching tricks.  ``quantize_boundary`` is the int8 round trip the
configuration's codec states (per-block absmax over each boundary
tensor's flattened elements), applied by the reference at the split.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Shape:
    """Sizes of one Swin detection configuration (a config file's keys)."""
    img_h: int
    img_w: int
    in_chans: int
    patch_size: int
    embed_dim: int
    depths: Tuple[int, ...]
    num_heads: Tuple[int, ...]
    window: int
    mlp_ratio: float
    num_classes: int
    fpn_dim: int
    norm_eps: float

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "Shape":
        kw = {}
        for f in dataclasses.fields(cls):
            v = cfg[f.name]
            kw[f.name] = tuple(v) if isinstance(v, list) else v
        return cls(**kw)

    @property
    def n_stages(self) -> int:
        return len(self.depths)

    def stage_dim(self, s: int) -> int:
        return self.embed_dim * 2 ** s

    def stage_hw(self, s: int) -> Tuple[int, int]:
        """Feature map (H, W) inside stage ``s``."""
        h = -(-self.img_h // self.patch_size)
        w = -(-self.img_w // self.patch_size)
        for _ in range(s):
            h, w = -(-h // 2), -(-w // 2)
        return h, w

    def padded_hw(self, s: int) -> Tuple[int, int]:
        h, w = self.stage_hw(s)
        k = self.window
        return -(-h // k) * k, -(-w // k) * k

    def mlp_dim(self, s: int) -> int:
        return int(self.stage_dim(s) * self.mlp_ratio)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _leaf_specs(sh: Shape):
    """Nested (shape, kind) template in the program's parameter layout.
    kind: 'w' dense matrix, 'c' convolution kernel (HWIO), 'b' bias,
    'g' norm gain, 'r' relative-position bias table, 'p' the
    classification prior bias."""
    C, fd, k = sh.embed_dim, sh.fpn_dim, sh.window
    tree: Dict[str, Any] = {
        "patch_embed": {"w": ((sh.patch_size, sh.patch_size, sh.in_chans, C),
                              "c"),
                        "b": ((C,), "b"), "norm_s": ((C,), "g"),
                        "norm_b": ((C,), "b")},
        "stages": [],
    }
    for s, depth in enumerate(sh.depths):
        d, hid, nh = sh.stage_dim(s), sh.mlp_dim(s), sh.num_heads[s]
        block = {
            "norm1_s": ((d,), "g"), "norm1_b": ((d,), "b"),
            "qkv_w": ((d, 3 * d), "w"), "qkv_b": ((3 * d,), "b"),
            "rel_bias": (((2 * k - 1) ** 2, nh), "r"),
            "proj_w": ((d, d), "w"), "proj_b": ((d,), "b"),
            "norm2_s": ((d,), "g"), "norm2_b": ((d,), "b"),
            "mlp": {"w1": ((d, hid), "w"), "b1": ((hid,), "b"),
                    "w2": ((hid, d), "w"), "b2": ((d,), "b")},
        }
        stage: Dict[str, Any] = {"blocks": [dict(block) for _ in range(depth)]}
        if s < sh.n_stages - 1:
            stage["merge"] = {"norm_s": ((4 * d,), "g"),
                              "norm_b": ((4 * d,), "b"),
                              "w": ((4 * d, 2 * d), "w")}
        tree["stages"].append(stage)
    tree["fpn"] = {
        "lateral": [((sh.stage_dim(s), fd), "w") for s in range(sh.n_stages)],
        "smooth": [((3, 3, fd, fd), "c") for _ in range(sh.n_stages)],
    }
    tree["det_head"] = {
        "conv1": ((3, 3, fd, fd), "c"), "conv2": ((3, 3, fd, fd), "c"),
        "cls_w": ((fd, sh.num_classes), "w"), "cls_b": ((sh.num_classes,), "p"),
        "box_w": ((fd, 4), "w"), "box_b": ((4,), "b"),
        "ctr_w": ((fd, 1), "w"), "ctr_b": ((1,), "b"),
    }
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _make_leaf(key, shape, kind):
    """Dense matrices: normal, std 0.02 (the Swin code's init of its
    linear layers).  Convolutions: normal, fan-in scaled.  Biases, gains
    and the bias table are random too, so the comparison covers them."""
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        return 0.02 * z
    if kind == "c":
        return z / math.sqrt(shape[0] * shape[1] * shape[2])
    if kind == "b":
        return 0.02 * z
    if kind == "g":
        return 1.0 + 0.1 * z
    if kind == "r":
        return 0.02 * z
    if kind == "p":                       # focal-loss prior, p = 0.01
        return jnp.full(shape, -math.log(99.0), jnp.float32)
    raise ValueError(kind)


def init_params(sh: Shape, seed: int):
    """Every weight of the model from ``seed``, made on the device in one
    jitted call, float32 (the type the configuration serves in)."""
    specs = _leaf_specs(sh)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_make_leaf(k, s, kind)
                      for k, (s, kind) in zip(keys, leaves)])

    return make(jax.random.key(np.uint32(seed % 2 ** 32)))


def param_count(sh: Shape) -> int:
    leaves = jax.tree.leaves(_leaf_specs(sh), is_leaf=_is_spec)
    return sum(int(np.prod(s)) for s, _ in leaves)


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------

def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _conv(x, w, stride: int, padding: str, prec):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)


def _regions(Hp: int, Wp: int, H: int, W: int, k: int, shift: int):
    """(Hp, Wp) region labels of the window mask.  Shifted layers: the
    Swin paper's nine regions of the cyclically shifted map.  Unshifted
    layers: the pad strip below H and right of W is a region of its own,
    as the configuration states."""
    lab = np.zeros((Hp, Wp), np.int32)
    if shift:
        cuts = (slice(0, -k), slice(-k, -shift), slice(-shift, None))
        n = 0
        for hs in cuts:
            for ws in cuts:
                lab[hs, ws] = n
                n += 1
    else:
        lab[H:, :] = 1
        lab[:, W:] = 2
    return lab


def _windows(x, k: int):
    """(B, Hp, Wp, C) -> (B, nWh, nWw, k*k, C)."""
    B, Hp, Wp, C = x.shape
    x = x.reshape(B, Hp // k, k, Wp // k, k, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, Hp // k, Wp // k,
                                                 k * k, C)


def _unwindows(x, k: int, Hp: int, Wp: int):
    B, nh, nw, _, C = x.shape
    x = x.reshape(B, nh, nw, k, k, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, Hp, Wp, C)


def _rel_index(k: int) -> np.ndarray:
    ys, xs = np.meshgrid(np.arange(k), np.arange(k), indexing="ij")
    ys, xs = ys.ravel(), xs.ravel()
    dy = ys[:, None] - ys[None, :] + k - 1
    dx = xs[:, None] - xs[None, :] + k - 1
    return dy * (2 * k - 1) + dx


def _attention(sh: Shape, p, h, H: int, W: int, nh: int, shift: int, prec):
    """Shifted-window multi-head self-attention of one block on the
    normalised map ``h`` (B, H, W, C).  Returns (B, H, W, C)."""
    k = sh.window
    B, _, _, C = h.shape
    hd = C // nh
    Hp, Wp = -(-H // k) * k, -(-W // k) * k
    h = jnp.pad(h, ((0, 0), (0, Hp - H), (0, Wp - W), (0, 0)))
    if shift:
        h = jnp.roll(h, (-shift, -shift), axis=(1, 2))
    qkv = jnp.einsum("bhwc,cd->bhwd", h, p["qkv_w"], precision=prec) \
        + p["qkv_b"]
    qkv = _windows(qkv, k)                           # (B, nh_, nw_, T, 3C)
    q, kk, v = (qkv[..., i * C:(i + 1) * C] for i in range(3))
    split = lambda t: t.reshape(t.shape[:-1] + (nh, hd))
    q, kk, v = split(q), split(kk), split(v)
    logits = jnp.einsum("bijqhd,bijkhd->bijhqk", q, kk,
                        precision=prec) / math.sqrt(hd)
    bias = p["rel_bias"][_rel_index(k)]              # (T, T, nh)
    logits = logits + jnp.transpose(bias, (2, 0, 1))
    lab = _windows(jnp.asarray(_regions(Hp, Wp, H, W, k, shift))[None, ...,
                                                                 None], k)
    same = lab[0, ..., 0][:, :, :, None] == lab[0, ..., 0][:, :, None, :]
    logits = jnp.where(same[None, :, :, None], logits, -jnp.inf)
    attn = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bijhqk,bijkhd->bijqhd", attn, v, precision=prec)
    out = out.reshape(out.shape[:-2] + (C,))
    out = jnp.einsum("bijtc,cd->bijtd", out, p["proj_w"],
                     precision=prec) + p["proj_b"]
    out = _unwindows(out, k, Hp, Wp)
    if shift:
        out = jnp.roll(out, (shift, shift), axis=(1, 2))
    return out[:, :H, :W]


def _block(sh: Shape, p, x, nh: int, shift: int, prec):
    H, W = x.shape[1], x.shape[2]
    x = x + _attention(sh, p, _ln(x, p["norm1_s"], p["norm1_b"], sh.norm_eps),
                       H, W, nh, shift, prec)
    m = p["mlp"]
    h = _ln(x, p["norm2_s"], p["norm2_b"], sh.norm_eps)
    h = jax.nn.gelu(jnp.einsum("bhwc,cd->bhwd", h, m["w1"], precision=prec)
                    + m["b1"], approximate=True)
    return x + jnp.einsum("bhwd,dc->bhwc", h, m["w2"], precision=prec) \
        + m["b2"]


def _merge(sh: Shape, p, x, prec):
    B, H, W, C = x.shape
    x = jnp.pad(x, ((0, 0), (0, H % 2), (0, W % 2), (0, 0)))
    H, W = x.shape[1], x.shape[2]
    x = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, H // 2, W // 2, 4 * C)
    x = _ln(x, p["norm_s"], p["norm_b"], sh.norm_eps)
    return jnp.einsum("bhwc,cd->bhwd", x, p["w"], precision=prec)


def _stage(sh: Shape, params, x, s: int, prec):
    """Blocks of stage ``s``.  Returns (stage output, merged input of the
    next stage or None)."""
    sp = params["stages"][s]
    for i, bp in enumerate(sp["blocks"]):
        x = _block(sh, bp, x, sh.num_heads[s],
                   0 if i % 2 == 0 else sh.window // 2, prec)
    return x, (_merge(sh, sp["merge"], x, prec) if "merge" in sp else None)


def _detect(sh: Shape, params, feats, prec):
    fpn, hp = params["fpn"], params["det_head"]
    mm = functools.partial(jnp.einsum, "bhwc,cd->bhwd", precision=prec)
    lat = [mm(f, w) for f, w in zip(feats, fpn["lateral"])]
    outs = [None] * len(lat)
    outs[-1] = lat[-1]
    for i in range(len(lat) - 2, -1, -1):
        up = jnp.repeat(jnp.repeat(outs[i + 1], 2, axis=1), 2, axis=2)
        outs[i] = lat[i] + up[:, :lat[i].shape[1], :lat[i].shape[2]]
    levels = []
    for o, w in zip(outs, fpn["smooth"]):
        o = _conv(o, w, 1, "SAME", prec)
        h = jax.nn.relu(_conv(o, hp["conv1"], 1, "SAME", prec))
        h = jax.nn.relu(_conv(h, hp["conv2"], 1, "SAME", prec))
        levels.append({
            "cls": mm(h, hp["cls_w"]) + hp["cls_b"],
            "box": jax.nn.relu(mm(h, hp["box_w"]) + hp["box_b"]),
            "ctr": mm(h, hp["ctr_w"]) + hp["ctr_b"],
        })
    return levels


def head_forward(sh: Shape, params, img, split: int, prec):
    """Patch embedding and stages 1..split.  Returns the boundary tensors
    in the order they cross the uplink: stage outputs 1..split, then the
    merged input of stage split+1 when there is one."""
    pe = params["patch_embed"]
    x = _conv(img, pe["w"], sh.patch_size, "VALID", prec) + pe["b"]
    x = _ln(x, pe["norm_s"], pe["norm_b"], sh.norm_eps)
    out = []
    for s in range(split):
        f, x = _stage(sh, params, x, s, prec)
        out.append(f)
    if split == 0 or split < sh.n_stages:
        out.append(x)
    return out


def tail_forward(sh: Shape, params, boundary: List[Any], split: int, prec):
    feats = list(boundary[:split])
    x = boundary[split] if split < sh.n_stages else None
    for s in range(split, sh.n_stages):
        f, x = _stage(sh, params, x, s, prec)
        feats.append(f)
    return _detect(sh, params, feats, prec)


def quantize_boundary(x, block: int):
    """The configuration's int8 round trip of one boundary tensor: its
    elements, flattened and zero-padded to whole blocks, each block scaled
    by absmax / 127, rounded half to even, clipped to [-127, 127]."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    flat = jnp.pad(flat, (0, (-n) % block)).reshape(-1, block)
    amax = jnp.max(jnp.abs(flat), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(flat / scale), -127, 127)
    return (q * scale).reshape(-1)[:n].reshape(x.shape)


def reference_forward(sh: Shape, params, img, split: int, quant_block: int,
                      dtype=jnp.float32):
    """Detections for ``img`` (B, H, W, 3) through the split at ``split``
    (None: no boundary, the whole model at the edge).  float32 runs at
    matmul precision 'highest'; any other ``dtype`` casts weights and
    input to it and computes in it throughout."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    img = img.astype(dtype)
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    if split is None:
        return tail_forward(sh, params, head_forward(sh, params, img, 0, prec),
                            0, prec)
    b = head_forward(sh, params, img, split, prec)
    b = [quantize_boundary(t, quant_block) for t in b]
    return tail_forward(sh, params, b, split, prec)
