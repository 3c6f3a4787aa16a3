"""Pallas TPU kernel: flash-decode (single new token vs. a long KV cache).

Grid = (B, KV, nk): kv blocks stream through VMEM innermost (sequential),
the running online-softmax state for all G = H//KV query heads of one kv
head sits in VMEM scratch.  The q tile is (G, hd) -- for GQA this makes the
MXU matmul (G x hd) @ (hd x block_kv), so grouped heads amortize the KV
stream (the roofline win of GQA at decode).

kv_len masking comes in as a (B, 1) int32 operand in SMEM-like layout
(block (1,1)), so ragged batches decode correctly against a pre-allocated
cache.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, block_kv: int, sm_scale: float):
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[0, 0]
    live = kj * block_kv < kv_len

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                     # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G,bkv)
        k_pos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, kv_len, *, block_kv: int = 512,
                            interpret: bool):
    """q: (B,1,H,hd); k,v: (B,S,KV,hd); kv_len: (B,) -> (B,1,H,hd)."""
    B, _, H, hd = q.shape
    _, S, KV, _ = k.shape
    G = H // KV
    block_kv = min(block_kv, S)
    nk = pl.cdiv(S, block_kv)
    pad_k = nk * block_kv - S
    qt = q.reshape(B, KV, G, hd)
    kt = k.transpose(0, 2, 1, 3)                                  # (B,KV,S,hd)
    vt = v.transpose(0, 2, 1, 3)
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    lens = kv_len.astype(jnp.int32).reshape(B, 1)

    kernel = functools.partial(_decode_kernel, block_kv=block_kv,
                               sm_scale=1.0 / math.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, n, j: (b, n, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, n, j: (b, n, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, n, j: (b, n, j, 0)),
            pl.BlockSpec((1, 1), lambda b, n, j: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, n, j: (b, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, lens)
    return out.reshape(B, 1, H, hd)
