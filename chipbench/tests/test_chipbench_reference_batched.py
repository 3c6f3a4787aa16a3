"""The reference against the program at a small Swin-B-patterned shape
(head dim 32 in every stage, as Swin-B's 128/4, and a deeper stage 2),
and on the edge's batched ``server_only`` path, on the CPU, where the
program's matmuls run in full float32: they must agree to rounding.
Also that each configuration file holds the program's published preset."""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from chipbench.lib import harness, model
from chipbench.tests.test_chipbench_reference import BLOCK, SMALL, _rel

from repro.configs.swin_t_detection import PUBLISHED
from repro.core.compression import ActivationCodec
from repro.core.splitting import SERVER_ONLY, SwinSplitPlan
from repro.models import swin as SW

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL_B = dict(SMALL, embed_dim=32, num_heads=[1, 2, 4, 8],
               depths=[1, 1, 3, 1])
SHAPES = {"small": SMALL, "swin_b_like": SMALL_B}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def setup(request):
    cfg = SHAPES[request.param]
    sh = model.Shape.from_config(cfg)
    params = model.init_params(sh, 2 ** 31 + 5)
    imgs = jax.random.uniform(jax.random.key(0), (2, 1, 64, 96, 3))
    return sh, harness.program_config(request.param, cfg), params, imgs


def _reference(sh, split):
    return jax.jit(lambda p, x: model.reference_forward(sh, p, x, split,
                                                        BLOCK))


def test_server_only_batch_of_two_matches_the_reference(setup):
    """Two different frames through the edge's batched tail, padded to a
    bucket of 2: each frame's detections are its own reference's, to f32
    rounding, and not the other frame's."""
    sh, pc, params, imgs = setup
    plan = SwinSplitPlan(pc, params)
    payloads = [plan.head(np.asarray(img), SERVER_ONLY)[0] for img in imgs]
    outs = plan.tail_batched(payloads, SERVER_ONLY, pad_to=2)
    refs = [_reference(sh, None)(params, img) for img in imgs]
    for out, ref in zip(outs, refs):
        assert _rel(out, ref) < 1e-5
    assert harness.rel_l2(outs[0], refs[1]) > 1e3 * harness.rel_l2(outs[0],
                                                                    refs[0])


def test_whole_model_matches_the_program_swin_b_like():
    sh = model.Shape.from_config(SMALL_B)
    pc = harness.program_config("swin_b_like", SMALL_B)
    params = model.init_params(sh, 2 ** 31 + 5)
    img = jax.random.uniform(jax.random.key(0), (1, 64, 96, 3))
    out = SW.forward_full_jit(pc)(params, img)
    # f32 matmuls on both sides: only rounding separates them
    assert _rel(out, _reference(sh, None)(params, img)) < 1e-5


@pytest.mark.parametrize("split", [1, 2, 3, 4])
def test_split_path_with_codec_matches_the_reference_swin_b_like(split):
    sh = model.Shape.from_config(SMALL_B)
    pc = harness.program_config("swin_b_like", SMALL_B)
    params = model.init_params(sh, 2 ** 31 + 5)
    img = jax.random.uniform(jax.random.key(0), (1, 64, 96, 3))
    plan = SwinSplitPlan(pc, params)
    codec = ActivationCodec(quant_block=BLOCK)
    payload, _ = plan.head(img, f"split{split}")
    view = codec.decompress(codec.compress(payload))
    out = plan.tail_batched([view], f"split{split}", pad_to=2)[0]
    # the int8 grids agree but for elements whose f32 value sits on a
    # rounding edge, so the gap is far below the int8 step itself
    assert harness.rel_l2(out, _reference(sh, split)(params, img)) < 2e-3


@pytest.mark.parametrize("name", ["swin_t", "swin_s", "swin_b"])
def test_configuration_file_is_the_published_preset(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert harness.program_config(name, cfg) == dataclasses.replace(
        PUBLISHED[name], name=name)
