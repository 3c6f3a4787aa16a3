"""Pallas TPU kernel: per-block absmax INT8 quantization (+ dequant).

This is step (1) of the paper's activation-compression pipeline
(FP32 -> INT8 before zlib).  It is also reused by the distributed-training
int8 gradient compressor (optim/compress.py).

TPU adaptation: the GPU version is a trivial elementwise pass; on TPU we
tile the flattened tensor into (rows=8k, lanes=128)-aligned VMEM blocks so
the VPU reduces absmax over a (BLOCK_ROWS, 128) tile per grid step, then
rescales in-register and emits int8.  One grid dimension, no DMA stalls:
block i streams HBM->VMEM while block i-1 computes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT8_MAX = 127.0
# Explicit reciprocal multiply for the scale: XLA rewrites the constant
# division ``absmax / 127`` into this multiply under jit but not in eager
# dispatch (a 1-ulp wobble between execution regimes).  Writing the multiply
# out keeps scales bitwise identical across eager / jit / interpret, which
# is what lets the fused codec (kernels/codec.py) and this per-tensor
# kernel produce interchangeable quantized grids.
INV_INT8_MAX = float(np.float32(1.0) / np.float32(INT8_MAX))
LANES = 128
BLOCK_ROWS = 64          # (64, 128) fp32 tile = 32 KiB VMEM per buffer


def smem_scale_spec():
    """BlockSpec for one f32 scale per grid step, held in SMEM as an
    (nb, 1, 1) array.  A rank-1 (1,) block is refused by the TPU lowering
    (rank-1 blocks must span 128 lanes); a per-step scalar belongs in
    SMEM.  Shared with kernels/codec.py."""
    return pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                  # (BLOCK_ROWS, LANES)
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.where(absmax > 0, absmax * INV_INT8_MAX, 1.0)
    q = jnp.clip(jnp.round(x / scale), -INT8_MAX, INT8_MAX)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[0, 0, 0] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0, 0, 0]


def quant_pallas(x, *, block: int = BLOCK_ROWS * LANES, interpret: bool):
    """x: arbitrary shape.  Returns (q int8 (nb, block), scales (nb,), n)."""
    assert block % LANES == 0
    rows = block // LANES
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    nb = flat.shape[0] // block
    if nb == 0:                              # empty leaf: nothing to launch
        return (jnp.zeros((0, block), jnp.int8),
                jnp.zeros((0,), jnp.float32), n)
    xb = flat.reshape(nb * rows, LANES)

    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            smem_scale_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * rows, LANES), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb)
    return q.reshape(nb, block), s.reshape(nb), n


def dequant_pallas(q, s, n, shape, dtype=jnp.float32, *, interpret: bool):
    """Inverse of quant_pallas."""
    nb, block = q.shape
    if nb == 0:
        return jnp.zeros(shape, dtype)
    rows = block // LANES
    qb = q.reshape(nb * rows, LANES)
    o = pl.pallas_call(
        _dequant_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            smem_scale_spec(),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * rows, LANES), jnp.float32),
        interpret=interpret,
    )(qb, s.reshape(nb, 1, 1))
    return o.reshape(-1)[:n].reshape(shape).astype(dtype)
