"""End-to-end equality for ``engine="vectorized"`` (core/engine_vec.py).

The vectorized MAC is opt-in per ``CellSimulator``; these tests run the
full simulator (lock-step and streaming, fixed and adaptive splits,
mobility handover, multi-cell batching) on BOTH engines and assert the
``FrameLog`` traces are field-exact -- including a replay of the
committed ``ran_streaming`` golden through the vectorized path, so the
fast engine is pinned to the same absolute trace as the oracle.
"""
import math

import numpy as np
import pytest

from repro.configs.swin_t_detection import CONFIG as SWIN_FULL
from repro.core.cell import CellSimulator
from repro.core.engine_vec import MultiCellVecMac, synthetic_city
from repro.core.mobility import (CellSite, MobilityConfig, MobilityModel,
                                 WaypointTrajectory)
from repro.core.ran import (MultiCell, RanCell, RanConfig, UplinkRequest,
                            make_policy)
from repro.core.splitting import SwinSplitPlan

from test_goldens import _controller, _system, _trace, load_golden, log_to_dict

POLICIES = ("rr", "pf", "edf")


def _logs_eq(a, b, tag):
    assert len(a) == len(b), (tag, len(a), len(b))
    for i, (x, y) in enumerate(zip(a, b)):
        dx, dy = log_to_dict(x), log_to_dict(y)
        for k in dx:
            vx, vy = dx[k], dy[k]
            if isinstance(vx, float) and math.isnan(vx):
                assert isinstance(vy, float) and math.isnan(vy), (tag, i, k)
            else:
                assert vx == vy, (tag, i, k, vx, vy)


@pytest.fixture(scope="module")
def plan():
    return SwinSplitPlan(SWIN_FULL, params=None)


@pytest.fixture(scope="module")
def system():
    return _system()


def test_golden_ran_streaming_vectorized(plan, system):
    """The committed ran_streaming golden (EDF streaming with capture
    jitter, bounded in-flight window, deadline drops) replays exactly
    through the vectorized engine."""
    want = load_golden("ran_streaming")
    sim = CellSimulator(plan=plan, system=system, n_ues=3, seed=11,
                        execute_model=False, frame_budget_s=3.0,
                        ran=RanCell(policy=make_policy("edf"),
                                    cfg=RanConfig(tti_s=0.005)),
                        engine="vectorized")
    res = sim.run_stream(_trace(), option="split3", fps=0.4,
                         jitter_s=0.05, inflight=2)
    got = [log_to_dict(l) for l in res.logs]
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        for k in w:
            if isinstance(w[k], float) and math.isnan(w[k]):
                assert isinstance(g[k], float) and math.isnan(g[k]), (i, k)
            else:
                assert g[k] == w[k], (i, k, g[k], w[k])


@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("adaptive", (False, True))
def test_lockstep_engines_match(plan, system, pol, adaptive):
    kw = dict(plan=plan, system=system, n_ues=3, seed=7,
              execute_model=False, frame_budget_s=2.0)
    if adaptive:
        kw["controller"] = _controller(system)
    option = None if adaptive else "split3"
    a = CellSimulator(ran=RanCell(policy=make_policy(pol),
                                  cfg=RanConfig(tti_s=0.002)),
                      **kw).run(_trace(), option=option)
    b = CellSimulator(ran=RanCell(policy=make_policy(pol),
                                  cfg=RanConfig(tti_s=0.002)),
                      **kw, engine="vectorized").run(_trace(), option=option)
    _logs_eq(a.logs, b.logs, ("lockstep", pol, adaptive))


@pytest.mark.parametrize("pol", ("rr", "pf"))
def test_streaming_engines_match(plan, system, pol):
    kw = dict(plan=plan, system=system, n_ues=3, seed=3,
              execute_model=False, frame_budget_s=2.5)
    a = CellSimulator(ran=RanCell(policy=make_policy(pol),
                                  cfg=RanConfig(tti_s=0.004)), **kw
                      ).run_stream(_trace(), option="split2", fps=0.5,
                                   jitter_s=0.03, inflight=2)
    b = CellSimulator(ran=RanCell(policy=make_policy(pol),
                                  cfg=RanConfig(tti_s=0.004)), **kw,
                      engine="vectorized"
                      ).run_stream(_trace(), option="split2", fps=0.5,
                                   jitter_s=0.03, inflight=2)
    _logs_eq(a.logs, b.logs, ("stream", pol))


def test_mobility_handover_engines_match(plan, system):
    """Two-cell ping-pong trajectory: handovers (and the dUPF path
    relocations they trigger) land on the same frames in both engines."""
    def build(engine):
        sites = [CellSite(0.0, 0.0), CellSite(400.0, 0.0)]
        traj = [WaypointTrajectory(((30.0, 0.0), (370.0, 0.0)),
                                   speed_mps=10.0, loop=True)
                for _ in range(3)]
        mob = MobilityModel(sites, traj,
                            MobilityConfig(a3_ttt_s=2.0,
                                           relocation_gap_s=0.2))
        cells = MultiCell([RanCell(policy=make_policy("edf"),
                                   cfg=RanConfig(tti_s=0.005))
                           for _ in sites])
        return CellSimulator(plan=plan, system=system, n_ues=3, seed=3,
                             execute_model=False, ran=cells, mobility=mob,
                             frame_budget_s=6.0, engine=engine)

    rssi = np.full((24, 3), -40.0)
    a = build("python").run_stream(rssi, option="split3", fps=0.5)
    b = build("vectorized").run_stream(rssi, option="split3", fps=0.5)
    assert a.stats.n_handovers == b.stats.n_handovers
    assert a.stats.n_handovers > 0
    _logs_eq(a.logs, b.logs, "mobility")


# ---------------------------------------------------------------------------
# multi-cell batched MAC
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pol", POLICIES)
def test_multicell_vec_mac_equality(pol):
    """``MultiCellVecMac.serve_slot`` batches all cells into one vmapped
    kernel call; per-cell results must match serving each oracle cell
    with its own paired generator."""
    for trial in range(3):
        rng = np.random.default_rng(100 * trial + 7)
        C = int(rng.integers(1, 4))
        cfg = RanConfig(n_prbs=int(rng.integers(8, 40)),
                        tti_s=float(rng.choice([1e-3, 2e-3])))
        cells = [RanCell(policy=make_policy(pol), cfg=cfg)
                 for _ in range(C)]
        mac = MultiCellVecMac(MultiCell(cells))
        kids = np.random.SeedSequence(trial).spawn(C)
        r_py = [np.random.default_rng(k) for k in kids]
        r_vec = [np.random.default_rng(k) for k in kids]
        for slot in range(3):
            reqs_all = []
            for _ in range(C):
                m = int(rng.integers(0, 9))
                reqs_all.append([UplinkRequest(
                    ue_id=int(u), n_bytes=int(rng.integers(0, 40_000)),
                    enqueue_s=float(rng.random() * 0.01),
                    deadline_s=float(0.02 + rng.random() * 0.2),
                    link_rate_bps=float(10 ** rng.uniform(6.5, 8.0)))
                    for u in rng.choice(60, size=m, replace=False)])
            got = mac.serve_slot(reqs_all, r_vec)
            for c in range(C):
                want = cells[c].serve_slot(reqs_all[c], r_py[c])
                assert set(want) == set(got[c]), (pol, trial, slot, c)
                for u in want:
                    for f in want[u].__dataclass_fields__:
                        va = getattr(want[u], f)
                        vb = getattr(got[c][u], f)
                        assert float(va) == float(vb) or (
                            np.isnan(va) and np.isnan(vb)), \
                            (pol, trial, slot, c, u, f, va, vb)
        for c in range(C):  # generators stayed paired modulo the tape
            a = r_py[c].random()
            b = (mac._tapes[c].buf[0] if mac._tapes[c].buf.size
                 else r_vec[c].random())
            assert a == b, (pol, trial, c, a, b)


def test_synthetic_city_partition():
    batches = synthetic_city(1000, 3, seed=1)
    assert len(batches) == 3
    assert sum(len(x["ue"]) for x in batches) == 1000


def test_vectorized_engine_refuses_a_tpu_backend(monkeypatch):
    """The scan kernels bitcast f64<->s64, which the TPU compiler refuses
    (tests/test_tpu_compile.py shows it): on a TPU backend the engine
    fails at construction instead of running anywhere else."""
    import jax
    from repro.core.ran_vec import VecRanCell
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="bitcast"):
        CellSimulator(plan=SwinSplitPlan(SWIN_FULL, params=None),
                      system=_system(), n_ues=2, engine="vectorized",
                      ran=RanCell(policy=make_policy("edf"),
                                  cfg=RanConfig()))
    with pytest.raises(NotImplementedError, match="bitcast"):
        VecRanCell.from_cell(RanCell(policy=make_policy("rr"),
                                     cfg=RanConfig()))
    # the python engine is untouched
    CellSimulator(plan=SwinSplitPlan(SWIN_FULL, params=None),
                  system=_system(), n_ues=2, engine="python",
                  ran=RanCell(policy=make_policy("edf"), cfg=RanConfig()))
