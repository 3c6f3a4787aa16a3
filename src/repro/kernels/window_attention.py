"""Pallas TPU kernels: Swin shifted-window attention.

The paper's backbone hot-spot.  Two entry points:

``window_attention_pallas`` -- the per-window kernel (one grid cell = one
window's (w2 x w2) attention).  TPU adaptation (DESIGN.md §2): a CUDA Swin
kernel maps one window to a thread block; on TPU we instead pad the window
token count w^2 (49) up to the sublane multiple (64) and make the grid
(window-batch, heads) -- every grid cell computes one window's full
attention in VMEM with a single pair of MXU matmuls, with the
relative-position bias and the shifted-window region mask fused into the
logits (no HBM round-trip for the bias).  Inputs are pre-padded by
ops.window_attention: q,k,v (nB, W2P, nh, hd), bias (nh, W2P, W2P),
mask (nB, W2P, W2P) int8 (1 = attend).

``fused_window_attention_pallas`` -- the whole-layer kernel (DESIGN.md
§13): ONE launch covers window partition + the shifted-window roll +
biased/masked attention + un-partition, consuming the image-layout qkv
projection (B, Hp, Wp, 3C) directly and emitting (B, Hp, Wp, C) back in
original coordinates.  The grid walks window-row bands; the H-axis roll
never materializes in HBM -- each step assembles its rolled band from two
consecutive original bands (modular index maps) and a VMEM carry holds
the ``shift`` rows that cross the band boundary on the way back out, so
every step writes one complete original-coordinate output band.  Inside
a band the attention runs one head at a time (``_window_core``): Mosaic's
matmul takes a single batch dim, here the band's windows.
``fused_window_attention_jnp`` is the bitwise-identical pure-jnp mirror
ops.py dispatches to off-TPU: it calls ``_window_core`` verbatim over all
windows at once (tests pin kernel == mirror exactly).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e9


def _window_kernel(q_ref, k_ref, v_ref, b_ref, m_ref, o_ref, *, sm_scale):
    q = q_ref[0, :, 0, :].astype(jnp.float32) * sm_scale     # (W2P, hd)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (W2P, W2P)
    s = s + b_ref[0].astype(jnp.float32)
    s = jnp.where(m_ref[0] > 0, s, NEG_INF)
    m = s.max(axis=1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / p.sum(axis=1, keepdims=True)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, :, 0, :] = o.astype(o_ref.dtype)


def window_attention_pallas(q, k, v, bias, mask, *, interpret: bool):
    """q,k,v: (nB, W2P, nh, hd); bias: (nh, W2P, W2P);
    mask: (nB, W2P, W2P) int8.  W2P and hd should be 64/128-aligned
    (ops.py pads).  Returns (nB, W2P, nh, hd)."""
    nB, W2P, nh, hd = q.shape
    kernel = functools.partial(_window_kernel, sm_scale=1.0 / math.sqrt(hd))
    return pl.pallas_call(
        kernel,
        grid=(nB, nh),
        in_specs=[
            pl.BlockSpec((1, W2P, 1, hd), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((1, W2P, 1, hd), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((1, W2P, 1, hd), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((1, W2P, W2P), lambda n, h: (h, 0, 0)),
            pl.BlockSpec((1, W2P, W2P), lambda n, h: (n, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, W2P, 1, hd), lambda n, h: (n, 0, h, 0)),
        out_shape=jax.ShapeDtypeStruct((nB, W2P, nh, hd), q.dtype),
        interpret=interpret,
    )(q, k, v, bias, mask)


# ---------------------------------------------------------------------------
# fused whole-layer kernel: partition + roll + attention + un-partition
# ---------------------------------------------------------------------------

def _window_core(x, bias, mask, *, n_heads: int, sm_scale: float):
    """Biased/masked softmax attention over pre-partitioned windows.

    x: (n, W2P, 3C) packed qkv, one padded window per leading index;
    bias: (nh, W2P, W2P) f32; mask: (n, W2P, W2P) int8 (1 = attend).
    Returns (n, W2P, C) f32.  One head at a time, so every matmul has a
    single batch dim (the window) -- the only form Mosaic's ``tpu.matmul``
    lowers.  Shared verbatim by the kernel body and the jnp mirror.
    """
    C = x.shape[-1] // 3
    hd = C // n_heads
    x = x.astype(jnp.float32)
    keep = mask.astype(jnp.int32) > 0    # v5e has no int8 vector compare
    outs = []
    for h in range(n_heads):
        q = x[..., h * hd:(h + 1) * hd] * sm_scale
        k = x[..., C + h * hd:C + (h + 1) * hd]
        v = x[..., 2 * C + h * hd:2 * C + (h + 1) * hd]
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = jnp.where(keep, s + bias[h], NEG_INF)    # (n, W2P, W2P)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        p = p / p.sum(axis=-1, keepdims=True)
        outs.append(jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                        preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=-1)


def _band_attention(band, bias, mask, *, window: int, n_heads: int,
                    w2: int, W2P: int, sm_scale: float):
    """Windowed attention over ONE window-row band.

    band: (window, Wp, 3C) packed qkv in image layout (already rolled when
    the layer shifts); bias: (nh, W2P, W2P) f32; mask: (nww, W2P, W2P)
    int8.  Partitions the band into its nww windows, pads w2 -> W2P, runs
    ``_window_core``, and un-partitions back to (window, Wp, C) f32.
    """
    Wp = band.shape[1]
    C = band.shape[2] // 3
    nww = Wp // window
    x = band.reshape(window, nww, window, 3 * C)
    x = x.transpose(1, 0, 2, 3).reshape(nww, w2, 3 * C)
    if W2P != w2:
        x = jnp.pad(x, ((0, 0), (0, W2P - w2), (0, 0)))
    o = _window_core(x, bias, mask, n_heads=n_heads, sm_scale=sm_scale)
    o = o[:, :w2].reshape(nww, window, window, C).transpose(1, 0, 2, 3)
    return o.reshape(window, Wp, C)


def _fused_kernel_noshift(qkv_ref, b_ref, m_ref, o_ref, *, window, n_heads,
                          w2, W2P, sm_scale):
    out = _band_attention(qkv_ref[0], b_ref[...], m_ref[0], window=window,
                          n_heads=n_heads, w2=w2, W2P=W2P, sm_scale=sm_scale)
    o_ref[0] = out.astype(o_ref.dtype)


def _fused_kernel_shift(a_ref, b_ref, bias_ref, mask_ref, o_ref, carry_ref, *,
                        window, shift, n_heads, w2, W2P, sm_scale):
    # Step t computes ROLLED band rb = (t + nwh - 1) % nwh, assembled from
    # original bands rb (rows shift..window) and rb+1 (rows 0..shift) --
    # the H roll -- then rolls W in-register.  Its first window-shift
    # output rows belong to original band rb; its last ``shift`` rows
    # belong to band rb+1 and wait one step in the VMEM carry.  Step 0
    # only primes the carry (its write target would be band nwh-1, whose
    # other rows come from the final step); steps 1..nwh each emit one
    # complete original-coordinate band.
    t = pl.program_id(1)
    a = a_ref[0]                                    # (window, Wp, 3C)
    b = b_ref[0]
    band = jnp.concatenate([a[shift:], b[:shift]], axis=0)
    band = jnp.concatenate([band[:, shift:], band[:, :shift]], axis=1)
    cur = _band_attention(band, bias_ref[...], mask_ref[0], window=window,
                          n_heads=n_heads, w2=w2, W2P=W2P, sm_scale=sm_scale)
    cur = jnp.concatenate([cur[:, -shift:], cur[:, :-shift]], axis=1)

    @pl.when(t > 0)
    def _write():
        o_ref[0] = jnp.concatenate(
            [carry_ref[...], cur[:window - shift]], axis=0).astype(o_ref.dtype)

    carry_ref[...] = cur[window - shift:]


DEFAULT_SCOPED_VMEM = 16 * 2 ** 20   # the TPU compiler's scoped-VMEM limit
VMEM_BYTES = 128 * 2 ** 20           # all of one v5e TensorCore's VMEM


def band_vmem_bytes(rows: int, Wp: int, C: int, n_heads: int, W2P: int,
                    shift: int, dtype) -> int:
    """VMEM one grid step of the fused band kernel holds, from its block
    shapes: a band of ``rows`` (the window) image rows, ``Wp`` wide, with
    ``C`` channels in ``n_heads`` heads, windows padded to ``W2P`` tokens.
    Every array is counted in VMEM's (8, 128) tiles: its last dimension
    padded to 128 lanes, the one before to 8 sublanes.

    Double-buffered by the pipeline: the qkv band (two when ``shift``
    rolls the map: the band and the next), the output band, the bias table
    and the band's mask.  Single: the ``shift``-row carry and the f32
    working set -- the assembled band, its partitioned padded windows,
    one head's logits and probabilities, the windows' output and the
    un-partitioned band.  An upper estimate of what the TPU compiler
    (v5e) counts: Swin-B's shifted stage-2 band 19.73 MiB against its
    16.28 MiB; Swin-T's shifted stage-0 band 19.18 MiB against 17.27-17.40
    MiB inside the batched whole-model program.
    """
    def tiles(*shape):
        *lead, a, b = shape
        n = -(-a // 8) * 8 * (-(-b // 128) * 128)
        for d in lead:
            n *= d
        return n

    it = jnp.dtype(dtype).itemsize
    nww = Wp // rows
    f32 = 4
    pipelined = (((2 if shift else 1) * tiles(rows, Wp, 3 * C)
                  + tiles(rows, Wp, C)) * it
                 + tiles(n_heads, W2P, W2P) * f32 + tiles(nww, W2P, W2P))
    working = f32 * (tiles(rows, Wp, 3 * C) + tiles(nww, W2P, 3 * C)
                     + 2 * tiles(nww, W2P, W2P) + tiles(nww, W2P, C)
                     + tiles(rows, Wp, C))
    return 2 * pipelined + tiles(shift, Wp, C) * f32 + working


def band_vmem_limit(rows: int, Wp: int, C: int, n_heads: int, W2P: int,
                    shift: int, dtype):
    """The scoped-VMEM limit the kernel asks for: None (the compiler's
    default) where ``band_vmem_bytes`` fits under it, else that estimate,
    never more than a v5e core holds."""
    need = band_vmem_bytes(rows, Wp, C, n_heads, W2P, shift, dtype)
    return None if need <= DEFAULT_SCOPED_VMEM else min(need, VMEM_BYTES)


def fused_window_attention_pallas(qkv, bias, mask, *, window: int, shift: int,
                                  n_heads: int, interpret: bool):
    """One-launch Swin window attention over a whole feature map.

    qkv: (B, Hp, Wp, 3C) packed projection in ORIGINAL image coordinates
    (Hp, Wp multiples of ``window``); bias: (nh, W2P, W2P) f32; mask:
    (nwh, nww, W2P, W2P) int8, indexed by (rolled) window-row band --
    ops.py builds both via ``_pad_fused_inputs``.  Returns (B, Hp, Wp, C)
    in original coordinates, qkv's dtype.

    shift == 0 is a direct grid (B, nwh): one step = one band in, one band
    out.  shift > 0 runs (B, nwh + 1) steps with the carry scheme above
    (band nwh-1 is visited twice; the extra step is the pipeline drain).
    VMEM per step: ``band_vmem_bytes``.  Where that lies above the
    compiler's scoped-VMEM default the call raises its limit to it
    (``band_vmem_limit``): the shifted bands of Swin-T's and Swin-S's
    stages 0, 1 and 3, Swin-B's shifted bands and its unshifted stage 3.
    Without it Swin-B's 512-wide shifted stage-2 band and, inside a
    whole-model program at batch > 1, the shifted stage-0 band are
    refused on a v5e.
    """
    B, Hp, Wp, C3 = qkv.shape
    C = C3 // 3
    w2 = window * window
    nwh = Hp // window
    W2P = mask.shape[-1]
    sm_scale = 1.0 / math.sqrt(C // n_heads)
    out_shape = jax.ShapeDtypeStruct((B, Hp, Wp, C), qkv.dtype)
    limit = band_vmem_limit(window, Wp, C, n_heads, W2P, shift, qkv.dtype)
    vmem = ({} if limit is None else
            {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)})
    bias_spec = pl.BlockSpec(bias.shape, lambda b, t: (0, 0, 0))
    mask_block = (1,) + mask.shape[1:]

    if shift == 0:
        kernel = functools.partial(
            _fused_kernel_noshift, window=window, n_heads=n_heads,
            w2=w2, W2P=W2P, sm_scale=sm_scale)
        return pl.pallas_call(
            kernel,
            grid=(B, nwh),
            in_specs=[
                pl.BlockSpec((1, window, Wp, C3), lambda b, t: (b, t, 0, 0)),
                bias_spec,
                pl.BlockSpec(mask_block, lambda b, t: (t, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, window, Wp, C),
                                   lambda b, t: (b, t, 0, 0)),
            out_shape=out_shape,
            interpret=interpret,
            **vmem,
        )(qkv, bias, mask)

    kernel = functools.partial(
        _fused_kernel_shift, window=window, shift=shift, n_heads=n_heads,
        w2=w2, W2P=W2P, sm_scale=sm_scale)
    band_spec = pl.BlockSpec((1, window, Wp, C3),
                             lambda b, t: (b, (t + nwh - 1) % nwh, 0, 0))
    next_spec = pl.BlockSpec((1, window, Wp, C3),
                             lambda b, t: (b, t % nwh, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, nwh + 1),
        in_specs=[
            band_spec,
            next_spec,
            bias_spec,
            pl.BlockSpec(mask_block, lambda b, t: ((t + nwh - 1) % nwh,
                                                   0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, window, Wp, C),
                               lambda b, t: (b, jnp.maximum(t - 1, 0), 0, 0)),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((shift, Wp, C), jnp.float32)],
        interpret=interpret,
        **vmem,
    )(qkv, qkv, bias, mask)


def fused_window_attention_jnp(qkv, bias, mask, *, window: int, shift: int,
                               n_heads: int):
    """Bitwise mirror of ``fused_window_attention_pallas`` in plain jnp.

    Same inputs/outputs.  The roll/partition steps are pure permutations
    and the per-window math is ``_window_core`` verbatim (over every
    window of the map at once -- each window's reductions keep the
    kernel's exact shapes and order), so the dispatch switch in ops.py
    cannot change a single bit (tests/test_kernels.py pins kernel ==
    mirror).
    """
    B, Hp, Wp, C3 = qkv.shape
    C = C3 // 3
    w2 = window * window
    nwh, nww = Hp // window, Wp // window
    W2P = mask.shape[-1]
    sm_scale = 1.0 / math.sqrt(C // n_heads)
    x = qkv
    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
    x = x.reshape(B, nwh, window, nww, window, C3).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B * nwh * nww, w2, C3)
    if W2P != w2:
        x = jnp.pad(x, ((0, 0), (0, W2P - w2), (0, 0)))
    mflat = jnp.broadcast_to(mask.reshape(1, nwh * nww, W2P, W2P),
                             (B, nwh * nww, W2P, W2P)).reshape(-1, W2P, W2P)
    o = _window_core(x, bias, mflat, n_heads=n_heads, sm_scale=sm_scale)
    o = o[:, :w2].reshape(B, nwh, nww, window, window, C)
    o = o.transpose(0, 1, 3, 2, 4, 5).reshape(B, Hp, Wp, C)
    if shift:
        o = jnp.roll(o, (shift, shift), axis=(1, 2))
    return o.astype(qkv.dtype)
