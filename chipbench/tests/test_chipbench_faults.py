"""A whole run of a cell, but for the look for a chip, on a small copy of
each cell on the CPU: sound, it is correct; with the timed path broken
underneath, ``correct`` comes out false.  And the control: the reference
computed in bfloat16, put in the program's place, fails the limit."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench.lib import faults, harness, model

PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
TINY = dict(img_h=56, img_w=56, embed_dim=16, depths=[1, 1, 2, 1],
            num_heads=[1, 2, 2, 4], fpn_dim=32)
CELLS = ["swin_t.split2.ue8.edf", "swin_s.server_only.ue8.b1",
         "swin_t.split2.ue1"]


def _tiny(cell):
    """The cell at a small size.  The pool keeps a frame for every UE, as
    the traffic files do, so a frame served another UE's detections is a
    wrong answer; the sample keeps the cell's size, so a fault in half of
    the frames is missed with odds of 2**-16."""
    spec = harness.load_cell(cell)
    return dataclasses.replace(
        spec, config=dict(spec.config, **TINY),
        traffic=dataclasses.replace(spec.traffic,
                                    pool_frames=max(spec.traffic.n_ues, 2)))


def _run(spec, traced=False):
    return harness.run(spec, 2 ** 31 + 17, 0.5, traced,
                       t_start=time.perf_counter(), require_tpu=False,
                       peaks=PEAKS)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(_tiny(cell))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in
                                   harness.load_cell(cell).end_to_end}


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS
    for f in faults.applicable(harness.load_cell(c).traffic)])
def test_broken_timed_path_is_not_correct(cell, fault):
    spec = _tiny(cell)
    with faults.planted(fault):
        res = _run(spec)
    c = res["checks"]["cls_rel_l2"]
    assert not res["correct"] and c["value"] > c["limit"], c


def _traced(monkeypatch, departure):
    """A traced run of the small ue8 cell with three parts of a few rounds
    each; the CPU's pace swings too widely between parts for the rule on
    departures, so each test sets it."""
    monkeypatch.setattr(harness, "MAX_DEPARTURE", departure)
    spec = _tiny("swin_t.split2.ue8.edf")
    spec = dataclasses.replace(
        spec, traffic=dataclasses.replace(spec.traffic, trace_seconds=0.3))
    return harness.run(spec, 2 ** 31 + 17, 0.9, True,
                       t_start=time.perf_counter(), require_tpu=False,
                       peaks=PEAKS)


SPAN_METRICS = ("loop_ms_per_frame", "mac_ms_per_frame", "head_ms_per_frame",
                "codec_ms_per_frame", "zlib_ms_per_frame", "tail_ms_per_frame")


def test_traced_run_reads_the_span_metrics(monkeypatch):
    res = _traced(monkeypatch, 1e9)
    m = res["metrics"]
    for k in SPAN_METRICS:
        assert m[k]["value"] > 0 and m[k]["unit"] == "ms/frame", k
    # no TPU in the trace: the device readers find nothing and say nothing
    for k in ("window_attn_roofline", "codec_kernel_roofline",
              "device_idle_share"):
        assert k not in m
    assert m["mfu"]["value"] > 0
    assert res["correct"] and "breakdown" in res
    assert res["device"]["window_s"] > 0


def test_a_traced_part_off_the_untraced_pace_reads_nothing(monkeypatch):
    res = _traced(monkeypatch, -1.0)       # every part departs
    assert set(res["metrics"]) == {"mfu"}, res["metrics"]
    assert res["device"]["busy_s"] == 0.0
    assert res["correct"]


@pytest.mark.parametrize("cell", ["swin_t.split2.ue8.edf",
                                  "swin_s.server_only.ue8.b1"])
def test_control_in_bfloat16_fails_the_limit(cell):
    """At the configuration's widths and depths, on a small image."""
    spec = harness.load_cell(cell)
    spec = dataclasses.replace(
        spec, config=dict(spec.config, img_h=64, img_w=96),
        traffic=dataclasses.replace(spec.traffic, pool_frames=2))
    sh = model.Shape.from_config(spec.config)
    params = model.init_params(sh, 7)
    pool = spec.traffic.pool(sh.img_h, sh.img_w)
    split, block = spec.traffic.split, spec.config["codec"]["quant_block"]
    bf16 = jax.jit(lambda p, x: model.reference_forward(
        sh, p, x, split, block, dtype=jnp.bfloat16))
    sample = harness.Sample(4, 7)
    for i in range(2):
        sample.offer(i, bf16(params, jnp.asarray(pool[i])))
    checks, bad = harness.check(spec, sh, params, pool, sample)
    c = checks["cls_rel_l2"]
    assert bad == 0 and c["value"] > c["limit"], c
