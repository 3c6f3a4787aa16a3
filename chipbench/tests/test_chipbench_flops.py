"""The benchmark's copied arithmetic against the program's, at the
published Swin-T and Swin-B shapes, and the weights it makes against the
layout the program takes."""
import json
import os

import jax
import numpy as np
import pytest

from chipbench.lib import flops, model

from repro.configs.swin_t_detection import SwinConfig
from repro.models import swin as SW

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    """A configuration file's sizes; ``swin_b`` is Swin-B's published
    sizes on Swin-T's file (the benchmark has no Swin-B cell: the
    program's window kernel does not compile for it on a v5e)."""
    with open(os.path.join(HERE, "configs", name.replace("swin_b", "swin_t")
                           + ".json")) as f:
        cfg = json.load(f)
    if name == "swin_b":
        cfg.update(embed_dim=128, depths=[2, 2, 18, 2],
                   num_heads=[4, 8, 16, 32])
    sh = model.Shape.from_config(cfg)
    prog = SwinConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                         for k, v in cfg.items()
                         if k in SwinConfig.__dataclass_fields__})
    return sh, prog


@pytest.mark.parametrize("name", ["swin_t", "swin_s", "swin_b"])
def test_model_flops_match_the_program(name):
    sh, prog = _load(name)
    assert flops.stage_flops(sh) == SW.stage_flops(prog)
    assert flops.total_flops(sh) == SW.total_flops(prog)
    for split in range(sh.n_stages + 1):
        assert flops.boundary_shapes(sh, split) == [
            tuple(s) for s in SW.boundary_shapes(prog, split)]


@pytest.mark.parametrize("name,gflop", [("swin_t", 210.1), ("swin_s", 284.0),
                                        ("swin_b", 401.1)])
def test_published_sizes(name, gflop):
    sh, _ = _load(name)
    assert flops.total_flops(sh) / 1e9 == pytest.approx(gflop, abs=0.05)


@pytest.mark.parametrize("name,mparams", [("swin_t", 31.4), ("swin_s", 52.8),
                                          ("swin_b", 90.8)])
def test_weights_have_the_program_layout(name, mparams):
    sh, prog = _load(name)
    theirs = jax.eval_shape(lambda: SW.init(prog, jax.random.PRNGKey(0)))
    mine = jax.eval_shape(lambda: model.init_params(sh, 3))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(mine)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(theirs)]
    assert model.param_count(sh) / 1e6 == pytest.approx(mparams, abs=0.05)


@pytest.mark.parametrize("name", ["swin_t", "swin_s", "swin_b"])
def test_window_kernel_count_is_the_attention_part_of_the_model(name):
    sh, _ = _load(name)
    for s in range(sh.n_stages):
        f, nbytes = flops.window_attn_call(sh, s, batch=2)
        Hp, Wp = sh.padded_hw(s)
        C = sh.stage_dim(s)
        n_win = Hp * Wp // sh.window ** 2
        assert f == 2 * 2 * n_win * sh.window ** 4 * C * 2
        assert nbytes > 2 * Hp * Wp * 4 * C * 4           # qkv in + out
    calls, f, _ = flops.window_attn_calls(sh, 0, sh.n_stages, 1)
    assert calls == sum(sh.depths)


def test_codec_stream_matches_the_program_packing():
    sh, prog = _load("swin_t")
    block = 8192
    n = flops.codec_stream_elements(sh, 2, 3, block)
    per = sum(-(-int(np.prod(s)) // block) * block
              for s in SW.boundary_shapes(prog, 2))
    assert n == 3 * per
    assert flops.codec_encode_bytes(n, block) == 5 * n + 4 * (n // block)
    assert flops.codec_decode_bytes(n, block) == 5 * n + 4 * (n // block)
