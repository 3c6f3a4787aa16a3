"""Calibrate the simulated testbed to the paper's measurements.

The paper's testbed (laptop UE + GH200 edge + physical NR uplink) is not
available; its *measured operating points* are.  We treat those as the
ground truth the simulator must hit:

  fitted constants                     from paper value
  ------------------------------------------------------------------
  UE effective FLOP/s                  UE-only E2E delay 3842.7 ms
  UE active power                      UE-only energy 0.0213 Wh/frame
  edge effective FLOP/s                server-only minus uplink+path
  R(-30), R(-10), R(-5)                Split-1 delays (Fig. 4)
  R(-40)                               server-only delay 327.6 ms
  R(-20)                               geometric interpolation

Everything else (other splits, other interference levels, energy
breakdowns, dUPF traces) is *predicted* by the simulator and compared to
the paper in EXPERIMENTS.md §Repro-validation -- that's the reproduction
test, not a re-fit.

The fit needs real compressed payload sizes.  They are measured by
running the actual Swin-T head + codec once per split at full detection
resolution, with weights and the input frame drawn from seed 0, and kept as
tracked data in ``swin_t_payloads.json`` next to this module; ``calibrate()``
only reads that table.  Regenerate it (about a minute on a CPU) with

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.core.calibration
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.configs.swin_t_detection import CONFIG as SWIN_CONFIG, SwinConfig
from repro.core.channel import ChannelModel, INTERFERENCE_LEVELS
from repro.core.compression import ActivationCodec
from repro.core.energy import DeviceProfile, RadioProfile
from repro.models import swin as SW

# --- paper §V measurements (ground truth for the fit / validation) ----------
PAPER = {
    "ue_only_ms": 3842.7,
    "server_only_ms": 327.6,
    "split1_ms": {-30: 1262.9, -10: 1586.1, -5: 2652.8},
    "ue_only_wh": 0.0213,
    "split1_wh": 0.0051,
    "server_only_wh": 0.0001,
    "privacy_split1": 0.527,
    "dupf_ms": (1944.13, 211.77),
    "cupf_ms": (2199.73, 310.58),
    "input_mb": 1.312,
    "payload_reduction": (0.85, 0.87),
}

PAYLOAD_TABLE = os.path.join(os.path.dirname(__file__), "swin_t_payloads.json")


@dataclass
class Calibrated:
    ue: DeviceProfile
    edge: DeviceProfile
    radio: RadioProfile
    channel: ChannelModel
    # measured-at-calibration payload bytes per option (batch=1)
    raw_bytes: Dict[str, int]
    compressed_bytes: Dict[str, int]
    swin_cfg: SwinConfig = field(default_factory=lambda: SWIN_CONFIG)

    def head_time_s(self, option: str) -> float:
        from repro.core.splitting import SwinSplitPlan
        plan = SwinSplitPlan.__new__(SwinSplitPlan)   # accounting only
        plan.cfg = self.swin_cfg
        plan.ship_merged = True
        plan.include_early_split = False
        return self.ue.compute_time_s(plan.head_flops(option))

    def tail_time_s(self, option: str) -> float:
        from repro.core.splitting import SwinSplitPlan
        plan = SwinSplitPlan.__new__(SwinSplitPlan)
        plan.cfg = self.swin_cfg
        plan.ship_merged = True
        plan.include_early_split = False
        return self.edge.compute_time_s(plan.tail_flops(option))

    def payload_bytes(self, plan, option: str,
                      codec: Optional[ActivationCodec] = None):
        """(raw, compressed) boundary bytes for any SplitPlan.  The tables
        are measurements of the paper's Swin plan at full resolution and
        apply to Swin plans only (accounting always charges the full-size
        calibrated system, even when a reduced stand-in executes); other
        plan families share option *names* but ship entirely different
        payloads, so they are estimated from their own payload specs with
        ``codec`` (default: the paper's int8+zlib setting)."""
        from repro.core.splitting import SERVER_ONLY, SwinSplitPlan
        if isinstance(plan, SwinSplitPlan) and option in self.raw_bytes:
            return self.raw_bytes[option], self.compressed_bytes[option]
        raw = plan.raw_payload_bytes(option)
        if option == SERVER_ONLY:
            return raw, raw                  # raw input ships as-is
        codec = codec or ActivationCodec()
        return raw, codec.estimate_bytes(plan.payload_specs(option))


def _measure_payloads(cfg: SwinConfig, codec: ActivationCodec,
                      seed: int = 0) -> Dict[str, Dict[str, int]]:
    """Run the real head + codec once per split at full resolution."""
    import jax
    import jax.numpy as jnp
    from repro.core.splitting import SwinSplitPlan, SERVER_ONLY, UE_ONLY
    from repro.data.video import SyntheticVideo, VideoConfig

    key = jax.random.PRNGKey(seed)
    params = SW.init(cfg, key)
    video = SyntheticVideo(VideoConfig(h=cfg.img_h, w=cfg.img_w, seed=seed))
    img = jnp.asarray(video.frame(0)[0])[None]
    plan = SwinSplitPlan(cfg, params)
    out = {}
    for opt in plan.options:
        payload, _ = plan.head(img, opt)
        if payload is None:
            out[opt] = {"raw": 0, "compressed": 0}
            continue
        if opt == SERVER_ONLY:
            # raw uint8 image over the link (paper's server-only mode)
            n = cfg.img_h * cfg.img_w * 3
            out[opt] = {"raw": n, "compressed": n}
            continue
        comp = codec.compress(payload)
        out[opt] = {"raw": int(comp.raw_bytes),
                    "compressed": int(comp.compressed_bytes)}
    return out


def load_payload_table(path: str = PAYLOAD_TABLE) -> Dict[str, Dict[str, int]]:
    """Per-split {"raw", "compressed"} boundary bytes of the published
    Swin-T at batch 1 (see the module docstring for how it is made)."""
    with open(path) as f:
        return json.load(f)


def calibrate() -> Calibrated:
    payloads = load_payload_table()

    cfg = SWIN_CONFIG
    total_f = SW.total_flops(cfg)

    # 1) UE compute rate from UE-only delay; power from UE-only energy.
    ue_t = PAPER["ue_only_ms"] / 1e3
    ue_flops = total_f / ue_t
    ue_power = PAPER["ue_only_wh"] * 3600.0 / ue_t
    ue = DeviceProfile("ue-laptop-i9", flops_per_s=ue_flops,
                       power_active_w=ue_power)

    # 2) Edge: GH200 MIG slice, 25x the laptop (fixed ratio; the residual
    #    of the server-only fit below lands on the uplink rate instead).
    edge = DeviceProfile("edge-gh200-mig", flops_per_s=25.0 * ue_flops,
                         power_active_w=250.0)

    path_s = 0.004  # dUPF local breakout (testbed default)

    # 3) Channel rates.  Split-1 delays pin R at -30/-10/-5; server-only
    #    pins R at -40 (input tx dominates); -20 geometric interp.
    h1 = SW.head_flops(cfg, 1) / ue.flops_per_s
    t1 = (total_f - SW.head_flops(cfg, 1)) / edge.flops_per_s
    c1 = payloads["split1"]["compressed"]
    rate_table: Dict[int, float] = {}
    for lvl, d_ms in PAPER["split1_ms"].items():
        tx = d_ms / 1e3 - h1 - t1 - path_s
        rate_table[lvl] = c1 * 8.0 / tx
    t_edge = total_f / edge.flops_per_s
    in_bytes = payloads["server_only"]["compressed"]
    tx0 = PAPER["server_only_ms"] / 1e3 - t_edge - path_s
    rate_table[-40] = in_bytes * 8.0 / tx0
    rate_table[-20] = float(np.sqrt(rate_table[-30] * rate_table[-10]))

    channel = ChannelModel(rate_table=rate_table)
    raw = {k: v["raw"] for k, v in payloads.items()}
    comp = {k: v["compressed"] for k, v in payloads.items()}
    return Calibrated(ue=ue, edge=edge, radio=RadioProfile(),
                      channel=channel, raw_bytes=raw, compressed_bytes=comp)


if __name__ == "__main__":
    with open(PAYLOAD_TABLE, "w") as f:
        json.dump(_measure_payloads(SWIN_CONFIG, ActivationCodec()), f,
                  indent=1)
        f.write("\n")
    print(f"wrote {PAYLOAD_TABLE}")
