"""Public kernel entry points.

Each op dispatches to the Pallas TPU kernel and pads inputs to
hardware-aligned tiles.  This module is the one place that decides how a
kernel runs: compiled on a TPU, and off-TPU either the interpreter
(``quantize``/``dequantize``/``window_attention``) or a bitwise-identical
jnp mirror (the codec pair and the attention kernels below).  The kernel
wrappers take ``interpret`` without a default.  ``ref.py`` holds the pure-jnp oracles the tests
compare against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import codec as _codec
from repro.kernels import quant as _quant
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import window_attention as _wa


@functools.cache
def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _interpret() -> bool:
    return not on_tpu()


# -- quant -------------------------------------------------------------------

def quantize(x, block: int = 8192):
    """Per-block absmax INT8 quant.  Returns (q (nb, block) int8, scales, n)."""
    return _quant.quant_pallas(x, block=block, interpret=_interpret())


def dequantize(q, scales, n, shape, dtype=jnp.float32):
    return _quant.dequant_pallas(q, scales, n, shape, dtype,
                                 interpret=_interpret())


# -- fused activation codec ---------------------------------------------------
#
# Unlike the ops above, the codec pair does NOT fall back to interpret mode
# off-TPU: the interpreter emulates the grid step-by-step (~100x slower than
# native XLA on CPU, measured in benchmarks/bench_compression.py), which
# would bury the single-launch win the codec exists for.  Every codec op is
# bitwise order-independent (absmax, round, clip, integer cumsum), so the
# pure-jnp path produces streams bit-identical to the kernel's; tests still
# validate the Pallas pair against ref.py via interpret=True directly.

def codec_encode(flat, block: int = 8192, delta: bool = False):
    """Single-launch payload encode: per-block absmax scales + int8 quant
    (+ block-local mod-256 row delta) over a packed block-aligned stream.
    Returns (stream (total,) uint8|int8, scales (nb,))."""
    if on_tpu():
        return _codec.codec_encode_pallas(flat, block=block, delta=delta,
                                          interpret=False)
    from repro.kernels import ref as _ref
    return _ref.codec_encode_ref(flat, block, delta)


def codec_decode(stream, scales, block: int = 8192, delta: bool = False):
    """Inverse of codec_encode; returns the dequantized (total,) f32 stream."""
    if on_tpu():
        return _codec.codec_decode_pallas(stream, scales, block=block,
                                          delta=delta, interpret=False)
    from repro.kernels import ref as _ref
    return _ref.codec_decode_ref(stream, scales, block, delta)


# -- attention ----------------------------------------------------------------
#
# Same dispatch contract as the codec pair: the Pallas kernel on real TPUs,
# a bitwise-identical pure-jnp path everywhere else (the serial interpreter
# is ~100x slower than native XLA on CPU and stays a test-only validation
# vehicle).  The jnp mirrors replay the kernels' exact blockwise
# online-softmax schedule -- same tile shapes, same masked NEG_INF
# reduction trees, same pl.when skip (as a select on untouched state) --
# so the switch cannot change a single output bit
# (tests/test_kernels.py pins mirror == interpret-mode kernel).

import math as _math


def _flash_attention_jnp(q, k, v, *, causal: bool, block_q: int,
                         block_kv: int):
    """Bitwise mirror of kernels/flash_attention.py."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq = -(-Sq // block_q)
    nk = -(-Skv // block_kv)
    sm_scale = 1.0 / _math.sqrt(hd)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if nq * block_q - Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, nq * block_q - Sq), (0, 0)))
    if nk * block_kv - Skv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, nk * block_kv - Skv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, nk * block_kv - Skv), (0, 0)))
    # GQA: the kernel's h // G index map, materialized as exact copies
    kt = jnp.repeat(kt, G, axis=1)
    vt = jnp.repeat(vt, G, axis=1)
    qb = qt.reshape(B, H, nq, block_q, hd).astype(jnp.float32) * sm_scale
    # XLA:CPU's BATCHED matvec reduces in a different order than the 2D
    # gemv the kernel's dot lowers to; gemm rows match gemv exactly, so a
    # tiny q block is padded up to the gemm path and row-sliced back
    BQP = max(block_q, 8)
    if BQP != block_q:
        qb = jnp.pad(qb, ((0, 0), (0, 0), (0, 0), (0, BQP - block_q), (0, 0)))
    kb = kt.reshape(B, H, nk, block_kv, hd).astype(jnp.float32)
    vb = vt.reshape(B, H, nk, block_kv, hd).astype(jnp.float32)
    offset = Skv - Sq
    q_lo = jnp.arange(nq) * block_q + offset                      # (nq,)
    q_pos = q_lo[:, None] + jnp.arange(BQP)[None]                 # (nq, bqp)
    m = jnp.full((B, H, nq, BQP), _fa.NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, nq, BQP), jnp.float32)
    acc = jnp.zeros((B, H, nq, BQP, hd), jnp.float32)
    for kj in range(nk):
        s = jax.lax.dot_general(qb, kb[:, :, kj],
                                (((4,), (3,)), ((0, 1), (0, 1))),
                                preferred_element_type=jnp.float32)
        k_pos = kj * block_kv + jnp.arange(block_kv)
        mask = jnp.broadcast_to((k_pos < Skv)[None, None],
                                (nq, BQP, block_kv))
        if causal:
            mask = mask & (k_pos[None, None] <= q_pos[:, :, None])
        s = jnp.where(mask[None, None], s, _fa.NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jax.lax.dot_general(
            p, vb[:, :, kj], (((4,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)
        if causal:
            # the kernel skips whole out-of-band kv blocks via pl.when;
            # the mirror computes them and keeps the state untouched
            in_band = kj * block_kv <= q_lo + block_q - 1         # (nq,)
            ib = in_band[None, None, :, None]
            m = jnp.where(ib, m_new, m)
            l = jnp.where(ib, l_new, l)
            acc = jnp.where(in_band[None, None, :, None, None], acc_new, acc)
        else:
            m, l, acc = m_new, l_new, acc_new
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    out = out[:, :, :, :block_q]
    return out.reshape(B, H, nq * block_q, hd)[:, :, :Sq].transpose(0, 2, 1, 3)


def _decode_attention_jnp(q, k, v, kv_len, *, block_kv: int):
    """Bitwise mirror of kernels/decode_attention.py."""
    B, _, H, hd = q.shape
    _, S, KV, _ = k.shape
    G = H // KV
    block_kv = min(block_kv, S)
    nk = -(-S // block_kv)
    sm_scale = 1.0 / _math.sqrt(hd)
    qt = q.reshape(B, KV, G, hd).astype(jnp.float32) * sm_scale
    # same batched-matvec caveat as the flash mirror: pad the G rows up to
    # the gemm path (gemm rows == the kernel's 2D gemv bits) and slice back
    GP = max(G, 8)
    if GP != G:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, GP - G), (0, 0)))
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if nk * block_kv - S:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, nk * block_kv - S), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, nk * block_kv - S), (0, 0)))
    kb = kt.reshape(B, KV, nk, block_kv, hd).astype(jnp.float32)
    vb = vt.reshape(B, KV, nk, block_kv, hd).astype(jnp.float32)
    lens = kv_len.astype(jnp.int32)
    m = jnp.full((B, KV, GP), _da.NEG_INF, jnp.float32)
    l = jnp.zeros((B, KV, GP), jnp.float32)
    acc = jnp.zeros((B, KV, GP, hd), jnp.float32)
    for kj in range(nk):
        s = jax.lax.dot_general(qt, kb[:, :, kj],
                                (((3,), (3,)), ((0, 1), (0, 1))),
                                preferred_element_type=jnp.float32)
        k_pos = kj * block_kv + jnp.arange(block_kv)
        s = jnp.where(k_pos[None, None, None] < lens[:, None, None, None],
                      s, _da.NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jax.lax.dot_general(
            p, vb[:, :, kj], (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)
        live = (kj * block_kv < lens)[:, None, None]              # dead kv
        m = jnp.where(live, m_new, m)                             # blocks:
        l = jnp.where(live, l_new, l)                             # pl.when
        acc = jnp.where(live[..., None], acc_new, acc)            # skip
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out[:, :, :G].reshape(B, 1, H, hd)


# The mirrors MUST run under jit: a Pallas kernel body is always compiled
# (even in interpret mode), and XLA:CPU contracts the online-softmax
# multiply-adds (acc * corr + dot) into FMAs inside a fused computation --
# op-by-op eager execution differs by 1 ulp.  jit'ing the mirror hands XLA
# the same expressions to contract, restoring exact equality (pinned in
# tests/test_kernels.py).  The caches also kill per-call retracing.

@functools.lru_cache(maxsize=None)
def _flash_jnp_jit(causal: bool, block_q: int, block_kv: int):
    return jax.jit(functools.partial(_flash_attention_jnp, causal=causal,
                                     block_q=block_q, block_kv=block_kv))


@functools.lru_cache(maxsize=None)
def _decode_jnp_jit(block_kv: int):
    return jax.jit(functools.partial(_decode_attention_jnp,
                                     block_kv=block_kv))


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128):
    if on_tpu():
        return _fa.flash_attention_pallas(q, k, v, causal=causal,
                                          block_q=block_q, block_kv=block_kv,
                                          interpret=False)
    return _flash_jnp_jit(causal, block_q, block_kv)(q, k, v)


def decode_attention(q, k, v, kv_len, *, block_kv: int = 512):
    if on_tpu():
        return _da.decode_attention_pallas(q, k, v, kv_len, block_kv=block_kv,
                                           interpret=False)
    return _decode_jnp_jit(block_kv)(q, k, v, kv_len)


def _pad_fused_inputs(bias, mask, *, window: int, nwh: int, nww: int):
    """Canonicalize fused-launch operands: pad bias/mask w2 -> W2P (64-lane
    multiple), apply the padded-query eye trick, and shape the mask per
    window-row band.

    bias: (nh, w2, w2); mask: (nW, w2, w2) bool or None (nW = nwh * nww).
    Returns (bias (nh, W2P, W2P) f32, mask (nwh, nww, W2P, W2P) int8).
    """
    nh, w2, _ = bias.shape
    W2P = -(-w2 // 64) * 64
    pad = W2P - w2
    if mask is None:
        mask = jnp.ones((nwh * nww, w2, w2), bool)
    if pad:
        bias = jnp.pad(bias, ((0, 0), (0, pad), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad), (0, pad)))
        # padded queries attend to themselves only (keeps softmax finite)
        eye = jnp.eye(W2P, dtype=bool)[None]
        mask = mask | (eye & (jnp.arange(W2P) >= w2)[None, :, None])
    return (bias.astype(jnp.float32),
            mask.astype(jnp.int8).reshape(nwh, nww, W2P, W2P))


def fused_window_attention(qkv, bias, mask=None, *, window: int, shift: int,
                           n_heads: int):
    """One-launch Swin window attention: partition + shifted roll + biased/
    masked softmax + un-partition (DESIGN.md §13).

    qkv: (B, Hp, Wp, 3C) packed projection in original image coordinates
    (Hp, Wp multiples of ``window``); bias: (nh, w2, w2); mask:
    (nW, w2, w2) bool or None, ordered by (rolled) window index.  Returns
    (B, Hp, Wp, C).  On TPU this is a single Pallas launch; elsewhere the
    bitwise-identical jnp mirror runs (same contract as the codec pair
    above -- the interpreter stays a test-only validation vehicle).
    """
    B, Hp, Wp, C3 = qkv.shape
    nwh, nww = Hp // window, Wp // window
    bias_p, mask_p = _pad_fused_inputs(bias, mask, window=window,
                                       nwh=nwh, nww=nww)
    if on_tpu():
        return _wa.fused_window_attention_pallas(
            qkv, bias_p, mask_p, window=window, shift=shift,
            n_heads=n_heads, interpret=False)
    return _wa.fused_window_attention_jnp(qkv, bias_p, mask_p, window=window,
                                          shift=shift, n_heads=n_heads)


def window_attention(q, k, v, bias, mask=None):
    """Swin windowed attention with padding to TPU tiles.

    q,k,v: (nB, w2, nh, hd); bias: (nh, w2, w2); mask: (nB, w2, w2) bool
    or None.  Pads w2 -> multiple of 64 and masks the padded tokens.
    """
    nB, w2, nh, hd = q.shape
    W2P = -(-w2 // 64) * 64
    pad = W2P - w2
    if mask is None:
        mask = jnp.ones((nB, w2, w2), bool)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad), (0, pad)))
        # padded queries attend to themselves only (keeps softmax finite)
        eye = jnp.eye(W2P, dtype=bool)[None]
        mask = mask | (eye & (jnp.arange(W2P) >= w2)[None, :, None])
    out = _wa.window_attention_pallas(q, k, v, bias.astype(jnp.float32),
                                      mask.astype(jnp.int8),
                                      interpret=_interpret())
    return out[:, :w2]
