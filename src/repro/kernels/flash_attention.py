"""Pallas TPU kernel: causal GQA flash attention (train / prefill).

Tiling (TPU adaptation of the GPU flash-attention schedule):
  grid = (B, H, nq, nk) with the kv axis innermost ("arbitrary" semantics:
  sequential on TPU), so the online-softmax state (m, l, acc) lives in VMEM
  scratch across kv steps -- the MXU sees (block_q x hd) @ (hd x block_kv)
  and (block_q x block_kv) @ (block_kv x hd) matmuls, both 128-aligned.

  GQA is folded into the index_map: q head h reads kv head h // G, so no
  KV replication is materialized in HBM.

Causality: kv blocks strictly above the diagonal are skipped via pl.when
(the grid is static; skipped steps cost control flow only, halving FLOPs
vs. a masked dense kernel).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_kv: int, seq_q: int, seq_kv: int,
                  causal: bool, sm_scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal band check (offset aligns q to the end of kv)
    offset = seq_kv - seq_q
    q_lo = qi * block_q + offset
    if causal:
        in_band = kj * block_kv <= q_lo + block_q - 1
    else:
        in_band = jnp.bool_(True)

    @pl.when(in_band)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_kv
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)

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


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool):
    """q: (B,S,H,hd); k,v: (B,Skv,KV,hd) -> (B,S,H,hd)."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Skv, block_kv)
    pad_q = nq * block_q - Sq
    pad_k = nk * block_kv - Skv
    qt = q.transpose(0, 2, 1, 3)                                  # (B,H,S,hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_kv=block_kv,
        seq_q=Sq, seq_kv=Skv, causal=causal, sm_scale=1.0 / math.sqrt(hd))

    import jax.experimental.pallas.tpu as pltpu

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :, :Sq].transpose(0, 2, 1, 3)
    return out
