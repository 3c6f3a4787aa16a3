"""The reference against the program, at a small size on the CPU, where
the program's matmuls run in full float32: they must agree to rounding.
A reference that drifted from the configuration's model would fail
every chip run; this catches it here."""
import jax
import numpy as np
import pytest

from chipbench.lib import harness, model

from repro.core.compression import ActivationCodec
from repro.core.splitting import SwinSplitPlan
from repro.models import swin as SW

SMALL = dict(img_h=64, img_w=96, in_chans=3, patch_size=4, embed_dim=32,
             depths=[2, 2, 2, 2], num_heads=[1, 2, 4, 8], window=7,
             mlp_ratio=4.0, num_classes=80, fpn_dim=64, norm_eps=1e-5,
             dtype="float32", attn_impl="pallas")
BLOCK = 8192


@pytest.fixture(scope="module")
def setup():
    sh = model.Shape.from_config(SMALL)
    params = model.init_params(sh, 2 ** 31 + 5)
    img = jax.random.uniform(jax.random.key(0), (1, 64, 96, 3))
    return sh, harness.program_config("small", SMALL), params, img


def _rel(out, ref):
    return max(
        float(np.linalg.norm(np.concatenate([np.ravel(o[k]) for o in out])
                             - np.concatenate([np.ravel(r[k]) for r in ref]))
              / np.linalg.norm(np.concatenate([np.ravel(r[k]) for r in ref])))
        for k in ("cls", "box", "ctr"))


def test_whole_model_matches_the_program(setup):
    sh, pc, params, img = setup
    out = SW.forward_full_jit(pc)(params, img)
    ref = jax.jit(lambda p, x: model.reference_forward(sh, p, x, None,
                                                       BLOCK))(params, img)
    assert _rel(out, ref) < 1e-5


@pytest.mark.parametrize("split", [1, 2, 3, 4])
def test_split_path_with_codec_matches_the_reference(setup, split):
    sh, pc, params, img = setup
    plan = SwinSplitPlan(pc, params)
    codec = ActivationCodec(quant_block=BLOCK)
    payload, _ = plan.head(img, f"split{split}")
    view = codec.decompress(codec.compress(payload))
    out = plan.tail_batched([view], f"split{split}", pad_to=2)[0]
    ref = jax.jit(lambda p, x: model.reference_forward(sh, p, x, split,
                                                       BLOCK))(params, img)
    # the int8 grids agree but for elements whose f32 value sits on a
    # rounding edge, so the gap is far below the int8 step itself
    assert harness.rel_l2(out, ref) < 2e-3
    noquant = jax.jit(lambda p, x: model.reference_forward(
        sh, p, x, None, BLOCK))(params, img)
    assert harness.rel_l2(out, noquant) > 10 * harness.rel_l2(out,
                                                                      ref)


def test_boundary_order_and_quantizer_match_the_codec(setup):
    sh, pc, params, img = setup
    payload = SW.head_apply(pc, params, img, 2)
    leaves = jax.tree.leaves(payload)
    mine = model.head_forward(sh, params, img, 2,
                              jax.lax.Precision.HIGHEST)
    assert [a.shape for a in leaves] == [a.shape for a in mine]
    codec = ActivationCodec(quant_block=BLOCK)
    view = jax.tree.leaves(codec.decompress(codec.compress(payload)))
    for x, v in zip(leaves, view):
        q = np.asarray(model.quantize_boundary(x, BLOCK))
        v = np.asarray(v)
        step = np.abs(np.asarray(x)).max() / 127
        assert np.mean(q == v) > 0.999
        assert np.max(np.abs(q - v)) <= step * 1.001
