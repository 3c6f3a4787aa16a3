"""Swin backbone internals: masks, merging, flops accounting, payloads."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.swin_t_detection import (CONFIG as FULL, PUBLISHED,
                                            SWIN_T, reduced)
from repro.models import swin as SW


def test_rel_pos_index_symmetric_range():
    idx = SW.rel_pos_index(7)
    assert idx.shape == (49, 49)
    assert idx.min() >= 0 and idx.max() < (2 * 7 - 1) ** 2
    assert (np.diag(idx) == idx[0, 0]).all()      # zero-offset bucket


def test_shift_mask_blocks_cross_region():
    m = SW.shift_attn_mask(14, 14, 7, 3)
    assert m.shape == (4, 49, 49)
    assert m[0].all()                  # first window: single region
    assert not m[-1].all()             # wrapped window: masked pairs exist
    assert (m[-1] & np.eye(49, dtype=bool)).diagonal().all()


def test_patch_merge_shapes():
    cfg = reduced()
    params = SW.init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 14, 14, cfg.embed_dim))
    y = SW.patch_merge(cfg, params["stages"][0]["merge"], x)
    assert y.shape == (1, 7, 7, 2 * cfg.embed_dim)


def test_stage_hw_and_dims():
    assert FULL.stage_hw(0) == (136, 200)
    assert FULL.stage_hw(3) == (17, 25)
    assert FULL.stage_dim(3) == 768


@pytest.mark.parametrize("name,dims,depths,heads,gflop,mparams", [
    ("swin_t", 96, (2, 2, 6, 2), (3, 6, 12, 24), 210.1, 31.4),
    ("swin_s", 96, (2, 2, 18, 2), (3, 6, 12, 24), 284.0, 52.8),
    ("swin_b", 128, (2, 2, 18, 2), (4, 8, 16, 32), 401.1, 90.8),
])
def test_published_presets(name, dims, depths, heads, gflop, mparams):
    """arXiv:2103.14030 §3.3 at the paper's 800x544 detection input."""
    cfg = PUBLISHED[name]
    assert (cfg.embed_dim, cfg.depths, cfg.num_heads, cfg.window,
            cfg.img_w, cfg.img_h) == (dims, depths, heads, 7, 800, 544)
    assert SW.total_flops(cfg) / 1e9 == pytest.approx(gflop, abs=0.05)
    shapes = jax.eval_shape(lambda: SW.init(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n / 1e6 == pytest.approx(mparams, abs=0.05)
    assert PUBLISHED["swin_t"] is SWIN_T is FULL


def test_flops_total_is_sum_of_parts():
    sf = SW.stage_flops(FULL)
    assert SW.total_flops(FULL) == sum(sf.values())
    assert SW.head_flops(FULL, 4) + sf["det"] == SW.total_flops(FULL)
    # monotone head flops
    hf = [SW.head_flops(FULL, s) for s in range(5)]
    assert hf == sorted(hf)


def test_paper_input_size():
    """Input payload must match the paper's stated 1.312 MB (uint8 RGB)."""
    n = FULL.img_h * FULL.img_w * 3
    assert abs(n / 2 ** 20 - 1.25) < 0.2          # ~1.3 MB
    # and activations are several x the input, motivating compression
    assert SW.boundary_bytes(FULL, 1) > 8 * n


def test_detection_loss_finite():
    cfg = reduced()
    params = SW.init(cfg, jax.random.PRNGKey(0))
    img = jax.random.uniform(jax.random.PRNGKey(1), (1, cfg.img_h, cfg.img_w, 3))
    levels = SW.forward_full(cfg, params, img)
    targets = []
    rng = np.random.default_rng(0)
    for lv in levels:
        B, H, W, _ = lv["cls"].shape
        targets.append({
            "cls": jnp.asarray(rng.integers(0, cfg.num_classes, (B, H, W))),
            "box": jnp.asarray(rng.uniform(0, 10, (B, H, W, 4)), jnp.float32),
            "pos": jnp.asarray(rng.random((B, H, W)) < 0.2),
        })
    loss = SW.detection_loss(cfg, levels, targets)
    assert np.isfinite(float(loss))


def test_pallas_window_attention_path_matches_xla():
    cfg_p = reduced()                 # pallas fused launch is the default
    assert cfg_p.attn_impl == "pallas"
    cfg_x = dataclasses.replace(cfg_p, attn_impl="xla")
    params = SW.init(cfg_p, jax.random.PRNGKey(0))
    img = jax.random.uniform(jax.random.PRNGKey(1), (1, cfg_p.img_h, cfg_p.img_w, 3))
    out_x = SW.forward_full(cfg_x, params, img)
    out_p = SW.forward_full(cfg_p, params, img)
    for a, b in zip(out_x, out_p):
        np.testing.assert_allclose(np.asarray(a["cls"]), np.asarray(b["cls"]),
                                   rtol=2e-4, atol=2e-4)


# -- host-side mask tables are cached (hot per-block path) --------------------

def test_mask_tables_cached():
    assert SW.rel_pos_index(7) is SW.rel_pos_index(7)
    assert SW.shift_attn_mask(14, 14, 7, 3) is SW.shift_attn_mask(14, 14, 7, 3)
    assert SW.pad_region_mask(14, 14, 10, 12, 7) \
        is SW.pad_region_mask(14, 14, 10, 12, 7)
    assert SW.shift_attn_mask(14, 14, 7, 3) is not SW.shift_attn_mask(21, 14, 7, 3)


# -- trace caches -------------------------------------------------------------

def test_head_apply_jit_cache_identity():
    cfg = reduced()
    assert SW.head_apply_jit(cfg, 1, True) is SW.head_apply_jit(cfg, 1, True)
    assert SW.head_apply_jit(cfg, 1, True) is not SW.head_apply_jit(cfg, 1, False)
    assert SW.head_apply_jit(cfg, 1, True) is not SW.head_apply_jit(cfg, 2, True)
    assert SW.tail_apply_jit(cfg, 1) is SW.tail_apply_jit(cfg, 1)
    assert SW.forward_full_jit(cfg) is SW.forward_full_jit(cfg)


# -- fused head->encode byte-identity (DESIGN.md §13) -------------------------

@pytest.fixture(scope="module")
def swin_fused():
    from repro.core.splitting import SwinSplitPlan
    cfg = reduced()
    params = SW.init(cfg, jax.random.PRNGKey(0))
    img = jax.random.uniform(jax.random.PRNGKey(2),
                             (1, cfg.img_h, cfg.img_w, 3))
    return cfg, params, img


def _assert_payloads_byte_identical(a, b):
    assert a.blobs == b.blobs
    assert len(a.scales) == len(b.scales)
    for sa, sb in zip(a.scales, b.scales):
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
    assert a.meta == b.meta
    assert a.raw_bytes == b.raw_bytes
    assert a.mode == b.mode and a.fused == b.fused


@pytest.mark.parametrize("split", [0, 1, 2, 3, 4])
@pytest.mark.parametrize("ship_merged", [True, False])
def test_fused_head_encode_byte_identity(swin_fused, split, ship_merged):
    """compress_head (head + quant epilogue in ONE device call) must emit
    the SAME bytes as compress() of the same jitted producer's output --
    for every split boundary and both payload layouts."""
    from repro.core.compression import ActivationCodec
    from repro.core.splitting import SwinSplitPlan, split_option
    cfg, params, img = swin_fused
    plan = SwinSplitPlan(cfg, params, ship_merged=ship_merged,
                         include_early_split=True)
    codec = ActivationCodec()
    assert codec.supports_fused()
    producer = plan.head_jitted(split_option(split))
    comp_f, tree_f = codec.compress_head(producer, params, img)
    tree_u = producer(params, img)
    comp_u = codec.compress(tree_u)
    assert comp_f.fused and comp_u.fused
    _assert_payloads_byte_identical(comp_f, comp_u)
    # the producer tree returned alongside is the same computation bitwise
    for lf, lu in zip(jax.tree.leaves(tree_f), jax.tree.leaves(tree_u)):
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(lu))
    # and the tail sees identical activations end to end
    out_f = plan.tail(codec.decompress(comp_f), split_option(split))
    out_u = plan.tail(codec.decompress(comp_u), split_option(split))
    for a, b in zip(out_f, out_u):
        np.testing.assert_array_equal(np.asarray(a["cls"]),
                                      np.asarray(b["cls"]))


@pytest.mark.parametrize("mode", ["int8", "int8_zlib", "int8_delta_zlib"])
def test_fused_head_encode_byte_identity_modes(swin_fused, mode):
    from repro.core.compression import ActivationCodec
    from repro.core.splitting import SwinSplitPlan
    cfg, params, img = swin_fused
    plan = SwinSplitPlan(cfg, params)
    codec = ActivationCodec(mode=mode)
    producer = plan.head_jitted("split1")
    comp_f, _ = codec.compress_head(producer, params, img)
    comp_u = codec.compress(producer(params, img))
    _assert_payloads_byte_identical(comp_f, comp_u)


def test_compress_head_falls_back_without_fused_mode(swin_fused):
    """Non-int8 codec modes can't fuse the epilogue; compress_head must
    refuse at supports_fused() so callers take the two-stage path."""
    from repro.core.compression import ActivationCodec
    codec = ActivationCodec(mode="zlib")
    assert not codec.supports_fused()
    codec2 = ActivationCodec(fused=False)
    assert not codec2.supports_fused()
