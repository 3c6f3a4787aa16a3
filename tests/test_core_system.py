"""Core system behaviour: channel, throughput estimator, privacy metric,
adaptive controller, E2E pipeline vs. the paper's measurements."""
import numpy as np
import pytest

from repro.core import calibration as C
from repro.core.adaptive import AdaptiveController, Objective
from repro.core.channel import (ChannelModel, INTERFERENCE_LEVELS, cupf_path,
                                dupf_path, iq_spectrogram, observe_kpms)
from repro.core.pipeline import SplitInferencePipeline
from repro.core.privacy import distance_correlation, payload_privacy
from repro.core.splitting import SERVER_ONLY, UE_ONLY, SwinSplitPlan
from repro.core.throughput import eval_estimator, train_estimator
from repro.configs.swin_t_detection import CONFIG as SWIN_FULL


@pytest.fixture(scope="module")
def system():
    return C.calibrate()          # reads the tracked payload table


@pytest.fixture(scope="module")
def accounting_pipeline(system):
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    from repro.core.compression import ActivationCodec
    return SplitInferencePipeline(
        plan=plan, system=system, codec=ActivationCodec(),
        controller=None, execute_model=False, seed=7)


# -- tracked payload table -----------------------------------------------------

def test_payload_table_raw_bytes_match_published_shapes():
    """The tracked table's raw bytes are the published Swin-T boundary
    tensors in f32 (and the uint8 image for server-only); compressed
    bytes are what the int8+zlib codec measured on them."""
    from repro.models import swin as SW
    table = C.load_payload_table()
    assert set(table) == {UE_ONLY, SERVER_ONLY, "split1", "split2", "split3",
                          "split4"}
    for l in range(1, 5):
        row = table[f"split{l}"]
        assert row["raw"] == SW.boundary_bytes(SWIN_FULL, l)
        assert 0 < row["compressed"] < row["raw"]
    n_img = SWIN_FULL.img_h * SWIN_FULL.img_w * 3
    assert table[SERVER_ONLY] == {"raw": n_img, "compressed": n_img}
    assert table[UE_ONLY] == {"raw": 0, "compressed": 0}


def test_calibrate_reads_only_the_tracked_table(system):
    table = C.load_payload_table()
    assert system.raw_bytes == {k: v["raw"] for k, v in table.items()}
    assert system.compressed_bytes == {k: v["compressed"]
                                       for k, v in table.items()}


# -- channel -------------------------------------------------------------------

def test_channel_monotone_in_interference(system):
    rates = [system.channel.mean_rate(i) for i in INTERFERENCE_LEVELS]
    assert rates == sorted(rates, reverse=True)


def test_channel_fading_is_bounded(system):
    rng = np.random.default_rng(0)
    rs = [system.channel.sample_rate(-20, rng) for _ in range(200)]
    mean = system.channel.mean_rate(-20)
    assert 0.5 * mean < np.median(rs) < 1.5 * mean


# -- calibration reproduces the paper's endpoints --------------------------------

def test_ue_only_delay_matches_paper(system, accounting_pipeline):
    logs = accounting_pipeline.run_trace([None], [-30], option=UE_ONLY)
    assert abs(logs[0].delay_s * 1e3 - C.PAPER["ue_only_ms"]) < 80


def test_server_only_delay_matches_paper(system, accounting_pipeline):
    logs = accounting_pipeline.run_trace([None] * 20, [-40] * 20,
                                         option=SERVER_ONLY)
    mean = np.mean([l.delay_s for l in logs]) * 1e3
    assert abs(mean - C.PAPER["server_only_ms"]) < 60


def test_split1_delay_matches_paper(system, accounting_pipeline):
    for lvl, want_ms in C.PAPER["split1_ms"].items():
        logs = accounting_pipeline.run_trace([None] * 30, [lvl] * 30,
                                             option="split1")
        mean = np.mean([l.delay_s for l in logs]) * 1e3
        assert abs(mean - want_ms) / want_ms < 0.15, (lvl, mean, want_ms)


def test_deep_splits_exceed_ue_only_under_severe_interference(
        system, accounting_pipeline):
    """Paper Fig. 4's crossover at -5 dB: split-4 E2E exceeds UE-only."""
    d = {}
    for opt in (UE_ONLY, "split1", "split4"):
        logs = accounting_pipeline.run_trace([None] * 30, [-5] * 30, option=opt)
        d[opt] = np.mean([l.delay_s for l in logs])
    assert d["split4"] > d[UE_ONLY]          # crossover reproduced
    assert d["split1"] < d[UE_ONLY]          # shallow split still wins


def test_ue_energy_matches_paper(system, accounting_pipeline):
    logs = accounting_pipeline.run_trace([None], [-30], option=UE_ONLY)
    wh = logs[0].energy_j / 3600
    assert abs(wh - C.PAPER["ue_only_wh"]) / C.PAPER["ue_only_wh"] < 0.05
    logs = accounting_pipeline.run_trace([None] * 10, [-30] * 10, option="split1")
    wh1 = np.mean([l.energy_j for l in logs]) / 3600
    # paper: 0.0051 Wh/frame at split-1 (76.1% reduction)
    assert wh1 < 0.5 * wh


def test_tx_energy_much_smaller_than_inference(system, accounting_pipeline):
    """Paper Fig. 7 (qualitative): computation, not transmission, dominates
    UE energy, increasingly so at deeper splits.  (The paper's 25-50x
    implies a larger UE-side compute share than our analytic Mask-RCNN
    cost model yields at shallow splits -- documented deviation in
    EXPERIMENTS.md §Repro-validation.)"""
    ratios = {}
    for opt in ("split1", "split2", "split3", "split4"):
        logs = accounting_pipeline.run_trace([None] * 20,
                                             list(INTERFERENCE_LEVELS) * 4,
                                             option=opt)
        e_inf = np.mean([l.energy_inf_j for l in logs])
        e_tx = np.mean([l.energy_tx_j for l in logs])
        ratios[opt] = e_inf / e_tx
    assert ratios["split1"] > 1.5
    assert ratios["split3"] > 4.0
    assert ratios["split4"] > 4.0
    assert ratios["split4"] > ratios["split1"]     # deeper -> compute-dominated


def test_tx_energy_rises_with_interference(system, accounting_pipeline):
    means = []
    for lvl in (-40, -20, -5):
        logs = accounting_pipeline.run_trace([None] * 30, [lvl] * 30,
                                             option="split2")
        means.append(np.mean([l.energy_tx_j for l in logs]))
    assert means[0] < means[1] < means[2]


def test_dupf_beats_cupf(system):
    """Paper Fig. 8: dUPF lower mean delay than cUPF, and lower delay
    variability on the component the paper attributes it to.

    Both pipelines run the same seed, so the radio term (fading over the
    interference trace, ~0.7 s std) is a *common* component of both delay
    series; the paper attributes cUPF's larger delay STD to the path's
    queueing jitter, so the std comparison is made on the delay net of
    the shared tx time.  Comparing raw-delay stds would test the paired
    series' sample-covariance noise (~1e-4 relative at n=200), not the
    path -- it flipped sign on the seed trace.  bench_dupf.py keeps
    reporting raw E2E mean AND std for the Fig. 8 comparison itself."""
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    from repro.core.compression import ActivationCodec
    out = {}
    for path in (dupf_path(), cupf_path()):
        pipe = SplitInferencePipeline(plan=plan, system=system,
                                      codec=ActivationCodec(),
                                      controller=None, path=path,
                                      execute_model=False, seed=3)
        trace = np.tile(INTERFERENCE_LEVELS, 40).tolist()
        logs = pipe.run_trace([None] * len(trace), trace, option="split2")
        d = np.array([l.delay_s for l in logs])
        net = np.array([l.delay_s - l.tx_s for l in logs])
        out[path.name] = (d.mean(), net.std())
    assert out["dUPF"][0] < out["cUPF"][0]
    assert out["dUPF"][1] < out["cUPF"][1]


# -- throughput estimator ----------------------------------------------------------

def test_spectrogram_features_beat_kpm_under_narrowband(system):
    """The paper's core estimation claim."""
    kpm = train_estimator(system.channel, "kpm", n_train=1500, steps=250)
    spec = train_estimator(system.channel, "kpm+spec", n_train=1500, steps=250)
    e_kpm = eval_estimator(kpm, system.channel, n=400)
    e_spec = eval_estimator(spec, system.channel, n=400)
    assert e_spec["narrowband_rel_err"] < e_kpm["narrowband_rel_err"] * 0.8


# -- privacy ------------------------------------------------------------------------

def test_dcor_identity_is_one():
    x = np.random.default_rng(0).normal(size=(24, 50)).astype(np.float32)
    assert abs(distance_correlation(x, x) - 1.0) < 1e-5


def test_dcor_independent_is_small():
    """Bias-corrected dCor of independent data is ~0 (the naive empirical
    estimator would read ~0.5 at this n)."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(40, 30)).astype(np.float32)
    y = rng.normal(size=(40, 30)).astype(np.float32)
    assert distance_correlation(x, y) < 0.15


def test_payload_privacy_endpoints():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(16, 100)).astype(np.float32)
    assert payload_privacy(x, {}) == 0.0                     # UE-only
    assert abs(payload_privacy(x, {"img": x}) - 1.0) < 1e-5  # server-only


# -- adaptive controller ---------------------------------------------------------------

def _controller(system, objective=None):
    est = train_estimator(system.channel, "kpm+spec", n_train=800, steps=150)
    prof = {UE_ONLY: 0.0, SERVER_ONLY: 1.0, "split1": 0.53,
            "split2": 0.42, "split3": 0.33, "split4": 0.27}
    return AdaptiveController(system=system, estimator=est,
                              objective=objective or Objective(),
                              path=dupf_path(), privacy_profile=prof)


def test_controller_prefers_offload_when_channel_good(system):
    ctrl = _controller(system, Objective(w_delay=1.0, w_energy=0.3,
                                         w_privacy=0.0))
    rng = np.random.default_rng(0)
    ctrl.interference_db = -40
    kpm = observe_kpms(-40, False, rng)
    spec = iq_spectrogram(-40, False, rng)
    opts = [UE_ONLY, "split1", "split2", "split3", "split4", SERVER_ONLY]
    d = ctrl.decide(kpm, spec, opts)
    assert d.option != UE_ONLY


def test_controller_respects_privacy_constraint(system):
    ctrl = _controller(system, Objective(w_delay=1.0, p_max=0.6))
    rng = np.random.default_rng(0)
    kpm = observe_kpms(-40, False, rng)
    spec = iq_spectrogram(-40, False, rng)
    opts = [UE_ONLY, "split1", "split2", SERVER_ONLY]
    d = ctrl.decide(kpm, spec, opts)
    assert d.option != SERVER_ONLY           # dCor 1.0 violates p_max
    assert d.privacy <= 0.6


def test_controller_backs_off_under_jamming(system):
    """Under severe interference the chosen split moves shallow/local."""
    ctrl = _controller(system, Objective(w_delay=1.0, w_energy=0.1,
                                         w_privacy=0.1, p_max=0.9))
    rng = np.random.default_rng(0)
    opts = [UE_ONLY, "split1", "split2", "split3", "split4"]
    ctrl.interference_db = -40
    good = ctrl.decide(observe_kpms(-40, False, rng),
                       iq_spectrogram(-40, False, rng), opts)
    ctrl._current = None                      # reset hysteresis
    ctrl.interference_db = -5
    bad = ctrl.decide(observe_kpms(-5, False, rng),
                      iq_spectrogram(-5, False, rng), opts)
    order = {o: i for i, o in enumerate(opts)}
    assert order[bad.option] <= order[good.option]


def test_controller_hysteresis_prevents_flapping(system):
    ctrl = _controller(system)
    rng = np.random.default_rng(0)
    opts = [UE_ONLY, "split1", "split2", SERVER_ONLY]
    choices = []
    for i in range(20):
        lvl = -20 + rng.normal(0, 1.5)
        ctrl.interference_db = lvl
        d = ctrl.decide(observe_kpms(lvl, False, rng),
                        iq_spectrogram(lvl, False, rng), opts)
        choices.append(d.option)
    switches = sum(a != b for a, b in zip(choices, choices[1:]))
    assert switches <= 4


# -- adaptive end-to-end: adaptation beats every fixed split under a dynamic trace --

def test_adaptive_beats_fixed_splits_on_dynamic_trace(system):
    plan = SwinSplitPlan(SWIN_FULL, params=None)
    from repro.core.compression import ActivationCodec
    ctrl = _controller(system, Objective(w_delay=1.0, w_energy=0.15,
                                         w_privacy=0.0))
    rng = np.random.default_rng(5)
    trace = rng.choice(INTERFERENCE_LEVELS, size=120,
                       p=[0.2, 0.2, 0.2, 0.2, 0.2]).tolist()

    def mean_delay(option, controller=None):
        pipe = SplitInferencePipeline(plan=plan, system=system,
                                      codec=ActivationCodec(),
                                      controller=controller,
                                      execute_model=False, seed=11)
        logs = pipe.run_trace([None] * len(trace), trace, option=option)
        return np.mean([l.delay_s for l in logs])

    adaptive = mean_delay(None, ctrl)
    fixed = {o: mean_delay(o) for o in
             [UE_ONLY, "split1", "split2", "split3", "split4"]}
    assert adaptive <= min(fixed.values()) * 1.10   # within 10% of best fixed
    assert adaptive < fixed[UE_ONLY]
