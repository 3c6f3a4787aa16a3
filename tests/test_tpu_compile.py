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

from repro.configs.swin_t_detection import CONFIG, SWIN_B
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


def _stage_geometry(stage: int, cfg=CONFIG):
    """(Hp, Wp, C, heads) of stage ``stage``'s window-padded feature map."""
    h, w = cfg.stage_hw(stage)
    win = cfg.window
    return (-(-h // win) * win, -(-w // win) * win, cfg.stage_dim(stage),
            cfg.num_heads[stage])


def test_stage_geometries_are_the_published_ones():
    assert [_stage_geometry(s) for s in range(4)] == [
        (140, 203, 96, 3), (70, 105, 192, 6), (35, 56, 384, 12),
        (21, 28, 768, 24)]
    assert [_stage_geometry(s, SWIN_B) for s in range(4)] == [
        (140, 203, 128, 4), (70, 105, 256, 8), (35, 56, 512, 16),
        (21, 28, 1024, 32)]


def _band_limit(stage: int, shift: int, cfg=CONFIG):
    _, Wp, C, nh = _stage_geometry(stage, cfg)
    return wa.band_vmem_limit(cfg.window, Wp, C, nh, 64, shift, jnp.float32)


def test_band_vmem_limit_from_the_block_shapes():
    """The limit the kernel asks for: the compiler's default wherever the
    band's estimate fits under it, else the estimate, which lies above
    the compiler's own count at the two bands that were refused without
    it (Swin-B's shifted stage 2: 16.28 MiB; Swin-T's shifted stage 0 in
    the batched whole-model program: 17.40 MiB)."""
    mib = 2 ** 20
    assert [_band_limit(s, 0) for s in range(4)] == [None] * 4
    assert _band_limit(2, 3) is None
    assert _band_limit(2, 3, SWIN_B) > 16.28 * mib
    assert _band_limit(0, 3) > 17.40 * mib
    for stage in range(4):
        for shift in (0, 3):
            for cfg in (CONFIG, SWIN_B):
                _, Wp, C, nh = _stage_geometry(stage, cfg)
                need = wa.band_vmem_bytes(7, Wp, C, nh, 64, shift,
                                          jnp.float32)
                limit = _band_limit(stage, shift, cfg)
                if need <= wa.DEFAULT_SCOPED_VMEM:
                    assert limit is None
                else:
                    assert limit == min(need, wa.VMEM_BYTES)
    assert wa.band_vmem_limit(7, 4096, 4096, 32, 64, 3, jnp.float32) == \
        wa.VMEM_BYTES


# Swin-T's cases keep the ids they had before Swin-B's joined them.
STAGES = ([pytest.param(CONFIG, s, id=str(s)) for s in range(4)]
          + [pytest.param(SWIN_B, s, id=f"swin_b-{s}") for s in range(4)])


@pytest.mark.parametrize("shift", [0, 3])
@pytest.mark.parametrize("cfg,stage", STAGES)
def test_fused_window_attention_compiles(one_chip, cfg, stage, shift):
    Hp, Wp, C, nh = _stage_geometry(stage, cfg)
    win = cfg.window
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


def test_batched_whole_model_compiles(one_chip, monkeypatch):
    """``swin_full`` at batch 2, the edge's batched ``server_only``
    program, with the Pallas kernels it runs on a TPU: its shifted
    stage-0 band is refused at the compiler's default VMEM limit."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    params = jax.eval_shape(lambda: SW.init(CONFIG, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    img = jax.ShapeDtypeStruct((2, CONFIG.img_h, CONFIG.img_w, 3),
                               jnp.float32, sharding=one_chip)
    compiled = _compile(lambda p, x: SW.forward_full(CONFIG, p, x),
                        params, img)
    text = compiled.as_text()
    for stage, depth in enumerate(CONFIG.depths):
        assert text.count(f"%swin.stage{stage}.") >= depth


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
