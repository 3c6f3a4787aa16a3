"""The batched ``server_only`` cell, run whole but for the look for a
chip, on a small copy of it on the CPU, as ``test_chipbench_faults.py``
runs the others: sound, it is correct; with the timed path broken
underneath, ``correct`` comes out false; a traced run, whose trace has
no TPU in it, reads its host spans and no kernel; and the bfloat16
control fails the cell's limit."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench.lib import faults, harness, model
from chipbench.tests.test_chipbench_faults import PEAKS, TINY

CELLS = ["swin_b.server_only.ue8"]


def _tiny(cell, **traffic):
    spec = harness.load_cell(cell)
    return dataclasses.replace(
        spec, config=dict(spec.config, **TINY),
        traffic=dataclasses.replace(spec.traffic, **traffic))


def _run(spec, traced=False, seconds=0.5):
    return harness.run(spec, 2 ** 31 + 23, seconds, traced,
                       t_start=time.perf_counter(), require_tpu=False,
                       peaks=PEAKS)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(_tiny(cell))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS
    for f in faults.applicable(harness.load_cell(c).traffic)])
def test_broken_timed_path_is_not_correct(cell, fault):
    with faults.planted(fault):
        res = _run(_tiny(cell))
    c = res["checks"]["cls_rel_l2"]
    assert not res["correct"] and c["value"] > c["limit"], c


# What a traced run reads with no TPU: the host's spans and clock.  The
# device-trace metrics, the stage-2 kernels' among them, read nothing.
HOST_READ = {"mfu", "tail_host_ms_per_frame", "copy_ms_per_frame"}
DEVICE_READ = {"window_attn_roofline", "device_idle_share",
               "tail_device_ms_per_frame", "window_attn_s2_roofline",
               "window_attn_s2_ms_per_frame"}


def _traced(cell, monkeypatch):
    monkeypatch.setattr(harness, "MAX_DEPARTURE", 1e9)
    spec = _tiny(cell, trace_seconds=0.3)
    return spec, _run(spec, traced=True, seconds=0.9)


def test_traced_run_reads_no_stage_kernel_without_a_tpu(monkeypatch):
    spec, res = _traced(CELLS[0], monkeypatch)
    assert {m["name"] for m in spec.per_layer} == HOST_READ | DEVICE_READ
    assert set(res["metrics"]) == HOST_READ and res["correct"]
    assert res["device"]["window_s"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_fails_the_limit(cell):
    """At the configuration's widths and depths (Swin-B's 512-wide stage 2
    among them), on a small image: the reference in bfloat16 in the
    program's place reads above the cell's limit."""
    spec = harness.load_cell(cell)
    spec = dataclasses.replace(
        spec, config=dict(spec.config, img_h=64, img_w=96),
        traffic=dataclasses.replace(spec.traffic, pool_frames=2))
    sh = model.Shape.from_config(spec.config)
    params = model.init_params(sh, 7)
    pool = spec.traffic.pool(sh.img_h, sh.img_w)
    block = spec.config["codec"]["quant_block"]
    bf16 = jax.jit(lambda p, x: model.reference_forward(
        sh, p, x, None, block, dtype=jnp.bfloat16))
    sample = harness.Sample(4, 7)
    for i in range(2):
        sample.offer(i, bf16(params, jnp.asarray(pool[i])))
    checks, bad = harness.check(spec, sh, params, pool, sample)
    c = checks["cls_rel_l2"]
    assert bad == 0 and c["value"] > c["limit"], c
