"""Pallas TPU kernel pair: fused activation codec (quant [+ delta]).

The activation-compression hot path used to be a serial per-leaf host
loop: one quant launch, one device->host transfer and one zlib call per
tensor, with the delta filter running as host-side numpy.  This kernel
pair encodes an entire payload pytree -- every boundary tensor of every
UE in a batch group -- in ONE device pass over a packed flat stream:

  encode: per grid step, one (rows, LANES) fp32 tile = one quant block.
          VPU reduces absmax over the tile, rescales in-register, emits
          int8, and (delta mode) applies the mod-256 row delta filter
          before the tile ever leaves the register file.
  decode: the inverse -- row cumsum mod 256 back to the signed int8
          grid, then dequantize against the per-block scale.

TPU tiling: the stream is laid out (nb*rows, LANES) with LANES=128; the
default quant_block=8192 gives (64, 128) fp32 tiles (32 KiB VMEM per
buffer) whose int8/uint8 outputs align to the (32, 128) int8 min tile.
One grid dimension, no DMA stalls: block i streams HBM->VMEM while
block i-1 computes.

The delta filter is block-local (stride = one sublane row = 128
elements; the first row of every block stays absolute), so grid steps
carry no cross-step state and the grid parallelizes/pipelines freely.
The geometry differs from the legacy host filter (image-row delta along
a spatial axis), but both are exactly invertible on the quantized grid,
so decompressed tensors are bit-identical whichever encoder produced
the stream (core/compression.py owns the format bookkeeping).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.quant import smem_scale_spec

INT8_MAX = 127.0
# same explicit reciprocal multiply as kernels/quant.py: bitwise-stable
# scales across eager/jit/interpret keep this stream on the exact quant
# grid of the per-tensor kernels
INV_INT8_MAX = float(np.float32(1.0) / np.float32(INT8_MAX))
LANES = 128


def _encode_kernel(x_ref, q_ref, s_ref, *, delta: bool):
    x = x_ref[...].astype(jnp.float32)                  # (rows, LANES)
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.where(absmax > 0, absmax * INV_INT8_MAX, 1.0)
    q = jnp.clip(jnp.round(x / scale), -INT8_MAX, INT8_MAX).astype(jnp.int32)
    if delta:
        # mod-256 delta down the sublane rows; row 0 ships absolute, so
        # the block decodes standalone (no cross-step carry)
        prev = jnp.pad(q[:-1], ((1, 0), (0, 0)))
        q_ref[...] = ((q - prev) % 256).astype(jnp.uint8)
    else:
        q_ref[...] = q.astype(jnp.int8)
    s_ref[0, 0, 0] = scale


def _row_cumsum(x):
    """Inclusive int32 prefix sum down the rows (Hillis-Steele: log2(rows)
    shifted adds).  Mosaic has no cumsum; integer adds are exact, so this
    equals ``jnp.cumsum(x, axis=0)`` bit for bit."""
    k = 1
    while k < x.shape[0]:
        x = x + jnp.pad(x[:-k], ((k, 0), (0, 0)))
        k *= 2
    return x


def _decode_kernel(q_ref, s_ref, o_ref, *, delta: bool):
    if delta:
        acc = _row_cumsum(q_ref[...].astype(jnp.int32)) % 256
        q = acc - jnp.where(acc > 127, 256, 0)          # back to signed grid
    else:
        q = q_ref[...].astype(jnp.int32)
    o_ref[...] = q.astype(jnp.float32) * s_ref[0, 0, 0]


def codec_encode_pallas(flat, *, block: int, delta: bool, interpret: bool):
    """flat: (total,) with total % block == 0 (caller packs + pads leaves).

    Returns (stream (total,) uint8|int8, scales (nb,) f32).  Quantization
    blocks are identical to kernels/quant.py (same absmax, same rounding),
    so per-leaf streams stay bit-compatible with the per-tensor path.
    """
    assert block % LANES == 0, "quant block must pack whole 128-lane rows"
    rows = block // LANES
    nb = flat.shape[0] // block
    assert nb * block == flat.shape[0], "stream must be block-aligned"
    xb = flat.reshape(nb * rows, LANES)
    q, s = pl.pallas_call(
        functools.partial(_encode_kernel, delta=delta),
        grid=(nb,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            smem_scale_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * rows, LANES),
                                 jnp.uint8 if delta else jnp.int8),
            jax.ShapeDtypeStruct((nb, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb)
    return q.reshape(-1), s.reshape(nb)


def codec_decode_pallas(stream, scales, *, block: int, delta: bool,
                        interpret: bool):
    """Inverse of codec_encode_pallas.  Returns (total,) f32 (callers slice
    per-leaf segments back out and cast to the leaf dtype)."""
    assert block % LANES == 0
    rows = block // LANES
    nb = scales.shape[0]
    qb = stream.reshape(nb * rows, LANES)
    o = pl.pallas_call(
        functools.partial(_decode_kernel, delta=delta),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            smem_scale_spec(),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * rows, LANES), jnp.float32),
        interpret=interpret,
    )(qb, scales.reshape(nb, 1, 1))
    return o.reshape(-1)
