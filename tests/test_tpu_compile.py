"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler is handed a ``v5e:2x2`` topology
description and compiles each kernel for its first device at the
published Swin-T widths.  Interpret-mode tests cannot see what this
catches -- Mosaic's tiling rules, its unsupported ops (two-batch-dim
matmuls, int8 vector compares, cumsum, rank-1 scale blocks) and the VMEM
limit.  Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

from repro.configs.swin_t_detection import CONFIG
from repro.kernels import codec as ck
from repro.kernels import quant as qk
from repro.kernels import window_attention as wa
from repro.models import swin as SW

QUANT_BLOCK = 8192          # ActivationCodec's default quant block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _stage_geometry(stage: int):
    """(Hp, Wp, C, heads) of stage ``stage``'s window-padded feature map."""
    h, w = CONFIG.stage_hw(stage)
    win = CONFIG.window
    return (-(-h // win) * win, -(-w // win) * win, CONFIG.stage_dim(stage),
            CONFIG.num_heads[stage])


def test_stage_geometries_are_the_published_ones():
    assert [_stage_geometry(s) for s in range(4)] == [
        (140, 203, 96, 3), (70, 105, 192, 6), (35, 56, 384, 12),
        (21, 28, 768, 24)]


@pytest.mark.parametrize("shift", [0, 3])
@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_fused_window_attention_compiles(one_chip, stage, shift):
    Hp, Wp, C, nh = _stage_geometry(stage)
    win = CONFIG.window
    W2P = 64
    assert shift in (0, win // 2)

    def fn(qkv, bias, mask):
        return wa.fused_window_attention_pallas(
            qkv, bias, mask, window=win, shift=shift, n_heads=nh,
            interpret=False)

    _compile(fn,
             jax.ShapeDtypeStruct((1, Hp, Wp, 3 * C), jnp.float32,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((nh, W2P, W2P), jnp.float32,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((Hp // win, Wp // win, W2P, W2P), jnp.int8,
                                  sharding=one_chip))


def _split1_stream_len():
    """Elements of the split-1 payload (stage-0 output + merged tensor),
    padded to whole quant blocks -- about 15.7 MB of f32."""
    n = SW.boundary_bytes(CONFIG, 1) // 4
    return -(-n // QUANT_BLOCK) * QUANT_BLOCK


@pytest.mark.parametrize("delta", [False, True])
def test_codec_encode_compiles(one_chip, delta):
    total = _split1_stream_len()
    assert total * 4 > 15_600_000

    def fn(flat):
        return ck.codec_encode_pallas(flat, block=QUANT_BLOCK, delta=delta,
                                      interpret=False)

    _compile(fn, jax.ShapeDtypeStruct((total,), jnp.float32,
                                      sharding=one_chip))


@pytest.mark.parametrize("delta", [False, True])
def test_codec_decode_compiles(one_chip, delta):
    total = _split1_stream_len()

    def fn(stream, scales):
        return ck.codec_decode_pallas(stream, scales, block=QUANT_BLOCK,
                                      delta=delta, interpret=False)

    _compile(fn,
             jax.ShapeDtypeStruct((total,), jnp.uint8 if delta else jnp.int8,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((total // QUANT_BLOCK,), jnp.float32,
                                  sharding=one_chip))


def test_quant_dequant_compile(one_chip):
    h, w = CONFIG.stage_hw(0)
    shape = (1, h, w, CONFIG.stage_dim(0))

    def fn(x):
        q, s, n = qk.quant_pallas(x, block=QUANT_BLOCK, interpret=False)
        return qk.dequant_pallas(q, s, n, shape, interpret=False)

    _compile(fn, jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip))


def test_vectorized_mac_seal_is_refused(one_chip):
    """The vectorized MAC's FMA seal bitcasts f64 <-> s64; the TPU
    compiler's X64 rewrite refuses that op, which is why
    ``engine='vectorized'`` refuses a TPU backend."""
    from repro.core.ran_vec import _seal

    def fn(a, b, z):
        return _seal(a * b, z) + a

    with jax.enable_x64(True):
        f64 = jax.ShapeDtypeStruct((1024,), jnp.float64, sharding=one_chip)
        z = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
        with pytest.raises(Exception, match="bitcast-convert"):
            jax.jit(fn).lower(f64, f64, z).compile()
