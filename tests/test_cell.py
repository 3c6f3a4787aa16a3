"""Multi-UE cell subsystem: SplitPlan protocol conformance, batched tail
equivalence, deadline-aware micro-batching accounting, seeded determinism,
vectorized channel sampling, and the self-describing codec payload."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.swin_t_detection import CONFIG as SWIN_FULL, reduced
from repro.core import calibration as C
from repro.core.cell import (CellSimulator, TailBatcher, TailRequest,
                             cell_interference_traces)
from repro.core.compression import ActivationCodec
from repro.core.splitting import (LMSplitPlan, SERVER_ONLY, SplitPlan,
                                  SwinSplitPlan, UE_ONLY, Workload)
from repro.models import swin as SW


@pytest.fixture(scope="module")
def system():
    return C.calibrate()


@pytest.fixture(scope="module")
def swin_exec():
    cfg = reduced()
    params = SW.init(cfg, jax.random.PRNGKey(0))
    plan = SwinSplitPlan(cfg, params)
    imgs = [jax.random.uniform(jax.random.PRNGKey(i),
                               (1, cfg.img_h, cfg.img_w, 3))
            for i in range(3)]
    return cfg, plan, imgs


# -- SplitPlan protocol -------------------------------------------------------

def test_plans_satisfy_protocol():
    swin = SwinSplitPlan(reduced(), params=None)
    lm = LMSplitPlan(get_reduced_config("smollm-360m"), params=None,
                     workload=Workload(n_tokens=16))
    for plan in (swin, lm):
        assert isinstance(plan, SplitPlan)
        for opt in plan.options:
            # uniform accounting signature -- no per-family extra args
            assert plan.head_flops(opt) >= 0
            assert plan.tail_flops(opt) >= 0
            assert plan.raw_payload_bytes(opt) >= 0


def test_lm_flops_scale_with_workload():
    cfg = get_reduced_config("smollm-360m")
    small = LMSplitPlan(cfg, None, workload=Workload(n_tokens=16))
    big = LMSplitPlan(cfg, None, workload=Workload(n_tokens=32))
    opt = small.options[1]
    assert big.head_flops(opt) == 2 * small.head_flops(opt)
    assert big.raw_payload_bytes(opt) == 2 * small.raw_payload_bytes(opt)


# -- batched tail execution ---------------------------------------------------

def test_tail_batched_matches_per_ue_tail(swin_exec):
    cfg, plan, imgs = swin_exec
    for opt in ("split1", "split3", SERVER_ONLY):
        payloads = [plan.head(im, opt)[0] for im in imgs]
        batched = plan.tail_batched(payloads, opt, pad_to=4)
        for p, got in zip(payloads, batched):
            want = plan.tail(p, opt)
            for lv_w, lv_g in zip(want, got):
                np.testing.assert_allclose(np.asarray(lv_w["cls"]),
                                           np.asarray(lv_g["cls"]),
                                           rtol=1e-4, atol=1e-4)


def test_tail_batched_padding_is_dropped(swin_exec):
    cfg, plan, imgs = swin_exec
    outs = plan.tail_batched([plan.head(imgs[0], "split2")[0]], "split2",
                             pad_to=4)
    assert len(outs) == 1
    assert outs[0][0]["cls"].shape[0] == 1


# -- micro-batching accounting ------------------------------------------------

def _edge(system, **kw):
    return dataclasses.replace(system.edge, launch_overhead_s=0.008,
                               batch_sat=3.0, **kw)


def test_batcher_groups_by_option(system):
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    batcher = TailBatcher(plan=plan, edge=_edge(system), max_wait_s=10.0)
    reqs = [TailRequest(ue_id=i, option="split1" if i % 2 else "split2",
                        arrival_s=0.1) for i in range(8)]
    served, records = batcher.run_slot(reqs)
    assert len(served) == 8
    assert len(records) == 2                       # one batch per option
    assert {r.option for r in records} == {"split1", "split2"}
    assert all(r.size == 4 and r.padded == 4 for r in records)


def test_batcher_deadline_closes_batches(system):
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    batcher = TailBatcher(plan=plan, edge=_edge(system), max_wait_s=0.05)
    # two arrival clusters further apart than the deadline
    reqs = [TailRequest(ue_id=i, option="split1", arrival_s=0.0 + 0.001 * i)
            for i in range(4)]
    reqs += [TailRequest(ue_id=4 + i, option="split1", arrival_s=1.0 + 0.001 * i)
             for i in range(4)]
    _, records = batcher.run_slot(reqs)
    assert len(records) == 2
    assert all(r.size == 4 for r in records)


def test_batched_beats_sequential_edge_time(system):
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    trace = cell_interference_traces(4, 32, seed=1)
    kw = dict(plan=plan, system=system, n_ues=32, seed=3, execute_model=False)
    on = CellSimulator(batching=True, **kw).run(trace, option="split2")
    off = CellSimulator(batching=False, **kw).run(trace, option="split2")
    assert on.stats.edge_busy_s < off.stats.edge_busy_s
    assert on.stats.mean_queue_s < off.stats.mean_queue_s
    # batching only changes the edge; the radio side is untouched
    for a, b in zip(on.logs, off.logs):
        assert a.tx_s == b.tx_s and a.rate_bps == b.rate_bps


# -- cell simulator -----------------------------------------------------------

def test_cell_seeded_determinism(system):
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    trace = cell_interference_traces(5, 16, seed=2)
    kw = dict(plan=plan, system=system, n_ues=16, seed=9, execute_model=False)
    sim = CellSimulator(**kw)
    a = sim.run(trace, option="split1")
    b = CellSimulator(**kw).run(trace, option="split1")
    assert a.logs == b.logs
    # repeated run() on ONE simulator resets seeded state and reproduces too
    assert sim.run(trace, option="split1").logs == a.logs
    c = CellSimulator(plan=plan, system=system, n_ues=16, seed=10,
                      execute_model=False).run(trace, option="split1")
    assert any(x.rate_bps != y.rate_bps for x, y in zip(a.logs, c.logs))


def test_cell_scales_to_hundreds_of_ues(system):
    """The vectorized accounting path: 256 UEs x 3 frames stays cheap."""
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    cell = CellSimulator(plan=plan, system=system, n_ues=256, seed=0,
                         execute_model=False)
    res = cell.run(cell_interference_traces(3, 256, seed=0), option="split1")
    assert len(res.logs) == 3 * 256
    assert res.stats.n_requests == 3 * 256
    assert 0.0 < res.stats.edge_utilization <= 1.0
    assert res.stats.mean_batch_occupancy <= 1.0


def test_cell_execute_model_detections_match_single_ue(system, swin_exec):
    """Batched edge execution produces the same detections the single-UE
    tail would -- the cell changes scheduling, not semantics."""
    cfg, plan, imgs = swin_exec
    # wide deadline: real quant_s includes one-off kernel warmup on the
    # first UE, which would otherwise fragment the batch
    cell = CellSimulator(plan=plan, system=system, n_ues=3, seed=0,
                         execute_model=True, batching=True, max_wait_s=30.0)
    res = cell.run(np.full((1, 3), -30.0), imgs=imgs, option="split1",
                   keep_outputs=True)
    codec = ActivationCodec()
    for i in range(3):
        # the cell ships payloads through the codec; compare like-for-like
        payload = codec.decompress(codec.compress(
            plan.head(imgs[i], "split1")[0]))
        want = plan.tail(payload, "split1")
        got = res.outputs[0][i]
        for lv_w, lv_g in zip(want, got):
            np.testing.assert_allclose(np.asarray(lv_w["cls"]),
                                       np.asarray(lv_g["cls"]),
                                       rtol=1e-3, atol=1e-3)
        assert res.logs[i].batch_size == 3


def test_cell_fused_head_matches_group_encode(system, swin_exec):
    """``fused_head=True`` (one device call per UE for head + quant
    epilogue) must produce byte-identical payload accounting and bitwise
    identical detections vs the group-encode baseline -- in BOTH the
    lock-step and the event engine."""
    cfg, plan, imgs = swin_exec
    trace = np.full((1, 3), -30.0)
    kw = dict(plan=plan, system=system, n_ues=3, seed=0, execute_model=True,
              batching=True, max_wait_s=30.0)
    a = CellSimulator(**kw).run(trace, imgs=imgs, option="split1",
                                keep_outputs=True)
    b = CellSimulator(fused_head=True, **kw).run(trace, imgs=imgs,
                                                 option="split1",
                                                 keep_outputs=True)
    for la, lb in zip(a.logs, b.logs):
        assert la.raw_bytes == lb.raw_bytes
        assert la.compressed_bytes == lb.compressed_bytes
    for i in range(3):
        for lv_a, lv_b in zip(a.outputs[0][i], b.outputs[0][i]):
            np.testing.assert_array_equal(np.asarray(lv_a["cls"]),
                                          np.asarray(lv_b["cls"]))
    # event engine: same byte identity through the streaming step-4 path
    sa = CellSimulator(**kw).run_stream(trace, fps=10.0, imgs=imgs,
                                        option="split1")
    sb = CellSimulator(fused_head=True, **kw).run_stream(trace, fps=10.0,
                                                         imgs=imgs,
                                                         option="split1")
    for la, lb in zip(sa.logs, sb.logs):
        assert la.raw_bytes == lb.raw_bytes
        assert la.compressed_bytes == lb.compressed_bytes


def test_cell_accounting_is_plan_generic(system):
    """An LM plan (options outside the Swin calibration tables) runs the
    accounting cell via spec-based payload estimation."""
    plan = LMSplitPlan(get_reduced_config("smollm-360m"), params=None,
                       workload=Workload(n_tokens=64))
    cell = CellSimulator(plan=plan, system=system, n_ues=8, seed=0,
                         execute_model=False)
    opt = plan.options[1]
    res = cell.run(np.full((2, 8), -20.0), option=opt)
    assert len(res.logs) == 16
    assert all(l.compressed_bytes > 0 and l.tx_s > 0 for l in res.logs)
    assert res.stats.n_requests == 16
    # option names collide with the Swin calibration tables ("split1");
    # the LM plan must account its OWN payload, not Swin's 3 MB feature maps
    assert res.logs[0].compressed_bytes <= plan.raw_payload_bytes(opt)


def test_cell_ue_only_bypasses_edge(system):
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    cell = CellSimulator(plan=plan, system=system, n_ues=8, seed=1,
                         execute_model=False)
    res = cell.run(np.full((2, 8), -30.0), option=UE_ONLY)
    assert res.stats.n_requests == 0
    assert all(l.tail_s == 0.0 and l.queue_s == 0.0 for l in res.logs)


# -- interference traces ------------------------------------------------------

def test_interference_traces_deterministic():
    a = cell_interference_traces(20, 7, seed=4)
    b = cell_interference_traces(20, 7, seed=4)
    np.testing.assert_array_equal(a, b)
    c = cell_interference_traces(20, 7, seed=5)
    assert (a != c).any()


def test_interference_traces_shape_and_levels():
    from repro.core.channel import INTERFERENCE_LEVELS
    tr = cell_interference_traces(50, 9, seed=1)
    assert tr.shape == (50, 9)
    assert set(np.unique(tr)) <= set(float(l) for l in INTERFERENCE_LEVELS)
    # sticky walk: consecutive frames move at most one level
    levels = np.asarray(INTERFERENCE_LEVELS, float)
    idx = np.searchsorted(levels, tr)
    assert np.abs(np.diff(idx, axis=0)).max() <= 1


def test_interference_traces_custom_levels():
    tr = cell_interference_traces(10, 3, seed=0, levels=(-12.0, -6.0),
                                  p_move=1.0)
    assert set(np.unique(tr)) <= {-12.0, -6.0}


# -- CellStats edge cases ------------------------------------------------------

def test_cellstats_zero_offload_slot():
    """A slot where no UE offloads: absorb_slot sees no records and every
    aggregate property stays finite (no division by zero)."""
    from repro.core.cell import CellStats
    st = CellStats()
    st.absorb_slot([], {})
    assert st.n_frames == 1 and st.n_requests == 0 and st.n_batches == 0
    assert st.edge_utilization == 0.0
    assert st.mean_batch_occupancy == 0.0
    assert st.mean_batch_size == 0.0
    assert st.mean_queue_s == 0.0
    assert st.drop_rate == 0.0 and st.mean_age_s == 0.0
    assert st.effective_fps == 0.0


def test_cellstats_empty_batch_records_via_simulator(system):
    """ue_only cell run: zero offloads end-to-end, stats stay clean."""
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    cell = CellSimulator(plan=plan, system=system, n_ues=4, seed=0,
                         execute_model=False)
    res = cell.run(np.full((3, 4), -30.0), option=UE_ONLY)
    st = res.stats
    assert st.n_frames == 3 and st.n_requests == 0
    assert st.span_s == 0.0 and st.edge_utilization == 0.0
    assert res.mean_delay_s > 0.0


def test_cellstats_absorb_batch_matches_slot_totals(system):
    """The event engine's per-batch absorption reaches the same request/
    busy/queue totals the per-slot form accumulates."""
    from repro.core.cell import BatchRecord, CellStats, ServedTail
    rec = BatchRecord(option="split1", size=3, padded=4, start_s=1.0,
                      compute_s=0.05)
    served = {i: ServedTail(tail_s=0.05, queue_s=0.01 * i, batch_size=3)
              for i in range(3)}
    a, b = CellStats(), CellStats()
    a.absorb_slot([rec], served)
    b.absorb_batch(rec, list(served.values()))
    assert (a.n_requests, a.n_batches) == (b.n_requests, b.n_batches)
    assert a.edge_busy_s == b.edge_busy_s
    assert a.occupancy_sum == b.occupancy_sum
    assert a.queue_sum_s == b.queue_sum_s


def test_interval_energy_edge_cases():
    """interval_energy_j (core/energy.py): zero-length runs, pure idle,
    and pipelined intervals where active time exceeds the wall span (the
    overlap case the per-frame accounting double-counts) -- idle clamps
    at zero, energy never goes negative."""
    from repro.core.energy import DeviceProfile, interval_energy_j
    p = DeviceProfile(name="ue", flops_per_s=1e12, power_active_w=30.0,
                      power_idle_w=2.0)
    assert interval_energy_j(p, 0.0, 0.0) == 0.0          # zero-length run
    assert interval_energy_j(p, 0.0, 5.0) == 2.0 * 5.0    # pure idle
    assert interval_energy_j(p, 3.0, 3.0) == 30.0 * 3.0   # wall fully active
    # overlapping intervals: the idle remainder clamps at zero
    assert interval_energy_j(p, 4.0, 3.0) == 30.0 * 4.0
    # monotone in both arguments
    assert interval_energy_j(p, 1.0, 10.0) < interval_energy_j(p, 2.0, 10.0)
    assert interval_energy_j(p, 1.0, 10.0) < interval_energy_j(p, 1.0, 20.0)


def _mk_log(ue, frame, dropped, delay_s=0.5, age_s=0.5, capture_s=0.0,
            deadline_s=float("inf")):
    from repro.core.pipeline import FrameLog
    return FrameLog(option="dropped" if dropped else "split2",
                    interference_db=-30.0,
                    delay_s=0.0 if dropped else delay_s,
                    head_s=0.1, quant_s=0.01, tx_s=0.1, path_s=0.01,
                    tail_s=0.05, energy_inf_j=0.0 if dropped else 1.0,
                    energy_tx_j=0.0, raw_bytes=0, compressed_bytes=0,
                    rate_bps=1e7, ue_id=ue, frame_idx=frame,
                    capture_s=capture_s, deadline_s=deadline_s,
                    age_s=0.0 if dropped else age_s, dropped=dropped)


def test_cellresult_accounting_when_all_frames_of_a_ue_drop():
    """A UE whose every capture was skipped: its logs are all dropped,
    every dropped frame counts as a deadline miss, and the cell-level
    delay/age means exclude it instead of averaging zeros in."""
    from repro.core.cell import CellResult, CellStats
    logs = ([_mk_log(0, k, dropped=False, delay_s=0.4, age_s=0.6)
             for k in range(3)]
            + [_mk_log(1, k, dropped=True, deadline_s=2.0)
               for k in range(3)])
    st = CellStats(n_completed=3, n_dropped=3, age_sum_s=1.8,
                   wall_s=3.0, n_ues=2)
    res = CellResult(logs=logs, stats=st)
    assert [l.dropped for l in res.ue_logs(1)] == [True] * 3
    assert res.drop_rate == 0.5
    assert res.mean_delay_s == pytest.approx(0.4)   # zeros NOT averaged in
    assert res.mean_age_s == pytest.approx(0.6)
    # dropped frames are misses even with a finite deadline in the future
    assert res.deadline_miss_rate == pytest.approx(0.5)
    assert st.drop_rate == 0.5
    assert st.mean_age_s == pytest.approx(0.6)
    assert st.effective_fps == pytest.approx(3 / 3.0 / 2)


def test_cellstats_all_dropped_accounting():
    """Degenerate streaming stats: nothing ever completed.  Every mean
    stays defined (zero), drop rate saturates at 1."""
    from repro.core.cell import CellResult, CellStats
    st = CellStats(n_completed=0, n_dropped=5, n_ues=1, wall_s=0.0)
    assert st.drop_rate == 1.0
    assert st.mean_age_s == 0.0
    assert st.effective_fps == 0.0
    res = CellResult(logs=[_mk_log(0, k, dropped=True) for k in range(5)],
                     stats=st)
    assert res.completed_logs == []
    assert res.mean_delay_s == 0.0 and res.mean_age_s == 0.0
    assert res.drop_rate == 1.0 and res.deadline_miss_rate == 1.0


def test_stream_per_ue_drop_accounting(system):
    """Driven through the event engine: per-UE dropped + completed
    always re-total the offered captures, and a UE's age mean comes from
    its completions only."""
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    from repro.core.ran import RanCell, RanConfig, make_policy
    sim = CellSimulator(plan=plan, system=system, n_ues=4, seed=3,
                        execute_model=False,
                        ran=RanCell(policy=make_policy("edf"),
                                    cfg=RanConfig(tti_s=0.005)))
    res = sim.run_stream(np.full((8, 4), -10.0), option="split2",
                         fps=2.0, inflight=1)
    assert res.stats.n_dropped > 0
    for u in range(4):
        logs = res.ue_logs(u)
        assert len(logs) == 8
        done = [l for l in logs if not l.dropped]
        assert len(done) + sum(l.dropped for l in logs) == 8
        if done:
            ages = [l.age_s for l in done]
            assert np.mean(ages) > 0.0
        # energy of dropped frames is zero (no head ran, no TX)
        for l in logs:
            if l.dropped:
                assert l.energy_j == 0.0 and l.delay_s == 0.0


# -- legacy radio regime stays bit-compatible with the RAN layer present ------

def test_legacy_uplink_formula_bit_compatible(system):
    """With ``ran=None`` (the default) the uplink is EXACTLY the pre-RAN
    formula: one vectorized sample_rate draw, tx = bytes/rate, then the
    path draw -- replayed here draw for draw."""
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    n, seed, lvl = 8, 21, -20.0
    sim = CellSimulator(plan=plan, system=system, n_ues=n, seed=seed,
                        execute_model=False)
    res = sim.run(np.full((1, n), lvl), option="split2")
    rng = np.random.default_rng(seed)
    rates = system.channel.sample_rate(np.full(n, lvl), rng,
                                       narrowband=np.zeros(n, bool))
    comp = system.compressed_bytes["split2"]
    path = rng.normal(0.0, 0.0, 0)  # no draws consumed between the stages
    exp_tx = system.channel.tx_time_s(np.full(n, comp, float), rates)
    for i, log in enumerate(res.logs):
        assert log.rate_bps == rates[i]
        assert log.tx_s == exp_tx[i]
        # and the RAN extension fields sit at their isolated-link defaults
        assert log.prb_share == 1.0 and log.harq_retx == 0
        assert log.deadline_s == float("inf") and not log.deadline_miss


def test_ran_mode_keeps_shared_rng_stream_aligned(system):
    """Switching the MAC on consumes the SAME shared-rng draws (one
    vectorized fading normal, then the path latencies), so RAN-vs-legacy
    comparisons are rng-paired: identical path jitter, same fading."""
    from repro.core.ran import RanCell, RanConfig, make_policy
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    kw = dict(plan=plan, system=system, n_ues=6, seed=5, execute_model=False)
    lv = np.full((2, 6), -30.0)
    legacy = CellSimulator(**kw).run(lv, option="split1")
    ran = CellSimulator(ran=RanCell(policy=make_policy("rr"),
                                    cfg=RanConfig(tti_s=0.005)),
                        **kw).run(lv, option="split1")
    for ll, lr in zip(legacy.logs, ran.logs):
        assert ll.path_s == lr.path_s


# -- vectorized channel -------------------------------------------------------

def test_vectorized_mean_rate_matches_scalar(system):
    lvls = np.array([-40.0, -33.3, -20.0, -12.5, -5.0])
    vec = system.channel.mean_rate(lvls)
    scalar = [system.channel.mean_rate(float(l)) for l in lvls]
    np.testing.assert_allclose(vec, scalar, rtol=1e-12)


def test_vectorized_sample_rate_shapes(system):
    rng = np.random.default_rng(0)
    r = system.channel.sample_rate(np.full(100, -20.0), rng,
                                   narrowband=np.arange(100) % 2 == 0)
    assert r.shape == (100,)
    assert (r >= system.channel.min_rate).all()


def test_vectorized_observe_kpms(system):
    from repro.core.channel import observe_kpms
    rng = np.random.default_rng(0)
    kpm = observe_kpms(np.full(64, -10.0), np.zeros(64, bool), rng)
    assert kpm.sinr_db.shape == (64,)
    assert (kpm.prb_util >= 0).all() and (kpm.prb_util <= 1).all()


# -- batched cell-side codec ---------------------------------------------------

def test_cell_group_encode_bit_identical_to_per_ue(swin_exec):
    """The cell's one-launch group encode (encode_group_stage ->
    compress_group) must produce per-UE payloads byte-identical to the
    per-UE path, and decode to bit-identical server views."""
    cfg, plan, imgs = swin_exec
    for mode in ("int8_zlib", "int8_delta_zlib"):
        codec = ActivationCodec(mode=mode)
        payloads = [plan.head(im, "split1")[0] for im in imgs]
        group = codec.compress_group(payloads)
        solo = [codec.compress(p) for p in payloads]
        for g, s in zip(group, solo):
            assert g.blobs == s.blobs
            np.testing.assert_array_equal(g.scales[0], s.scales[0])
            assert g.compressed_bytes == s.compressed_bytes
        views = codec.decompress_group(group)
        for vg, s in zip(views, solo):
            vs = codec.decompress(s)
            for lg, ls in zip(jax.tree.leaves(vg), jax.tree.leaves(vs)):
                np.testing.assert_array_equal(np.asarray(lg), np.asarray(ls))


def test_encode_group_stage_accounts_per_ue(system, swin_exec):
    """Group encode shares the launch but keeps per-UE byte accounting
    (each UE's uplink is charged for its own blob)."""
    from repro.core.pipeline import encode_group_stage, encode_stage
    cfg, plan, imgs = swin_exec
    payloads = [plan.head(im, "split1")[0] for im in imgs]
    codec = ActivationCodec()
    encs = encode_group_stage(plan, system, codec, payloads, "split1", True,
                              [None] * len(payloads))
    for e, p in zip(encs, payloads):
        solo = encode_stage(plan, system, codec, p, "split1", True)
        assert e.compressed_bytes == solo.compressed_bytes
        assert e.raw_bytes == solo.raw_bytes
        assert e.quant_s > 0


# -- self-describing codec payload -------------------------------------------

def test_payload_records_codec_mode():
    x = {"x": jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 4))}
    enc = ActivationCodec(mode="int8_delta_zlib")
    p = enc.compress(x)
    assert p.mode == "int8_delta_zlib"
    # a receiver constructed with a DIFFERENT default must still decode right
    dec = ActivationCodec(mode="int8_zlib")
    out = dec.decompress(p)
    np.testing.assert_allclose(np.asarray(out["x"]), np.asarray(x["x"]),
                               atol=0.1)
    # and byte-identically to the matching-mode decoder
    np.testing.assert_array_equal(
        np.asarray(out["x"]),
        np.asarray(ActivationCodec(mode="int8_delta_zlib").decompress(p)["x"]))
