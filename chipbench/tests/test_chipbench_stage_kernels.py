"""The stage-2 window-kernel readers on a hand-built trace: events are
told by the stage's name scope, the count has to hold, and a program
whose kernels carry no stage label reads nothing."""
import types

import pytest

from chipbench.lib import flops, harness, model, stage_kernels, trace as T

S2 = ('%swin.stage2.{n} = f32[8,35,56,512]{{3,2,1,0:T(8,128)}} custom-call('
      'f32[8,35,56,1536]{{3,2,1,0:T(8,128)}} %convolution_add_fusion.20, '
      'f32[16,64,64]{{2,1,0:T(8,128)}} %copy-done.36, '
      's8[5,8,64,64]{{3,2,1,0:T(8,128)(4,1)}} %copy-done.40), '
      'custom_call_target="tpu_custom_call"')
S0 = S2.replace("swin.stage2", "swin.stage0").replace(
    "s8[5,8,64,64]", "s8[20,29,64,64]")
UNLABELLED = S2.replace("swin.stage2", "swin_full")
FUSION = "%swin.stage2.3 = f32[8,35,56,512]{3,2,1,0} fusion(%a), kind=kLoop"


def _ctx(names, rounds=2):
    spec = harness.load_cell("swin_b.server_only.ue8")
    ops = [T.Op(name, 100.0 * i, 100.0 * i + 40.0)
           for i, name in enumerate(names)]
    return types.SimpleNamespace(
        trace=T.Trace(ops=ops, start_ns=0), window_ns=(0.0, 1e9),
        rounds=rounds, traffic=spec.traffic,
        shape=model.Shape.from_config(spec.config),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _names(n_s2):
    return ([S2.format(n=i) for i in range(n_s2)]
            + [S0.format(n=i) for i in range(4)] + [FUSION])


def test_stage_kernels_are_told_by_their_scope():
    assert stage_kernels.is_stage_kernel(S2.format(n=7), 2)
    assert not stage_kernels.is_stage_kernel(S2.format(n=7), 0)
    assert not stage_kernels.is_stage_kernel(S0.format(n=1), 2)
    assert not stage_kernels.is_stage_kernel(UNLABELLED.format(n=1), 2)
    assert not stage_kernels.is_stage_kernel(FUSION, 2)


def test_stage_calls_of_the_batch8_cell():
    ctx = _ctx([])
    calls, ops, nbytes = stage_kernels.stage_calls(ctx, 2)
    one = flops.window_attn_call(ctx.shape, 2, 8)
    assert calls == 2 * 18
    assert (ops, nbytes) == (calls * one[0], calls * one[1])


def test_readers_read_the_stage2_calls():
    roof = harness.load_reader("window_attn_s2_roofline")
    per_frame = harness.load_reader("window_attn_s2_ms_per_frame")
    ctx = _ctx(_names(36))
    ns = 36 * 40.0
    _, ops, nbytes = stage_kernels.stage_calls(ctx, 2)
    assert roof.read(ctx) == pytest.approx(T.roofline_pct(
        ops, nbytes, ns * 1e-9, 197e12, 819e9))
    assert per_frame.read(ctx) == pytest.approx(ns * 1e-6 / 16)


@pytest.mark.parametrize("names", [
    _names(35),                                           # one missing
    [UNLABELLED.format(n=i) for i in range(36)],          # no stage labels
])
def test_readers_read_nothing_when_the_count_does_not_hold(names):
    ctx = _ctx(names)
    for metric in ("window_attn_s2_roofline", "window_attn_s2_ms_per_frame"):
        assert harness.load_reader(metric).read(ctx) is None
    ctx.trace = None
    assert harness.load_reader("window_attn_s2_roofline").read(ctx) is None
