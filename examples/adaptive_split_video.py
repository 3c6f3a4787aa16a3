"""End-to-end driver: real-time video object detection with adaptive split
inference over the simulated AI-RAN network (the paper's full demo loop).

Every frame REALLY executes: Swin head on the "UE", Pallas INT8+zlib codec,
simulated 5G uplink (calibrated to paper Fig. 4), Swin tail + detection on
the "edge", while the AF adapts the split to the interference trace.

    PYTHONPATH=src python examples/adaptive_split_video.py [--frames 40]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.swin_t_detection import reduced
from repro.core import ActivationCodec, SwinSplitPlan, calibrate
from repro.core.adaptive import AdaptiveController, Objective
from repro.core.channel import dupf_path
from repro.core.pipeline import SplitInferencePipeline
from repro.core.splitting import SERVER_ONLY, UE_ONLY
from repro.core.throughput import train_estimator
from repro.data.video import SyntheticVideo, VideoConfig
from repro.models import swin as SW
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=40)
    ap.add_argument("--narrowband", action="store_true")
    args = ap.parse_args()

    cfg = reduced()
    params = SW.init(cfg, jax.random.PRNGKey(0))
    video = SyntheticVideo(VideoConfig(h=cfg.img_h, w=cfg.img_w, seed=0))
    imgs = [jnp.asarray(video.frame(t)[0])[None] for t in range(args.frames)]

    system = calibrate()
    est = train_estimator(system.channel, "kpm+spec", n_train=1500, steps=250)
    ctrl = AdaptiveController(
        system=system, estimator=est,
        objective=Objective(w_delay=1.0, w_energy=0.15, w_privacy=0.05),
        path=dupf_path(),
        privacy_profile={UE_ONLY: 0.0, SERVER_ONLY: 1.0, "split1": 0.53,
                         "split2": 0.42, "split3": 0.33, "split4": 0.27})
    pipe = SplitInferencePipeline(
        plan=SwinSplitPlan(cfg, params), system=system,
        codec=ActivationCodec(), controller=ctrl, path=dupf_path(),
        narrowband=args.narrowband, execute_model=True, seed=0)

    # interference ramps up mid-clip, then recovers (jammer sweep)
    t = np.linspace(0, 1, args.frames)
    trace = -40 + 35 * np.exp(-((t - 0.55) / 0.18) ** 2)

    print(f"{'frame':>5s} {'intf':>6s} {'option':12s} {'delay':>8s} "
          f"{'payload':>9s} {'energy':>7s}")
    logs = []
    for i, (img, lvl) in enumerate(zip(imgs, trace)):
        log = pipe.run_frame(img, float(lvl))
        logs.append(log)
        print(f"{i:5d} {lvl:5.0f}dB {log.option:12s} "
              f"{log.delay_s * 1e3:6.0f} ms {log.compressed_bytes / 1e3:7.0f}kB "
              f"{log.energy_j:6.2f} J")

    d = np.asarray([l.delay_s for l in logs])
    print(f"\nmean E2E delay {d.mean() * 1e3:.0f} ms  p95 {np.quantile(d, .95) * 1e3:.0f} ms")
    opts = [l.option for l in logs]
    print("split usage:", {o: opts.count(o) for o in sorted(set(opts))})
    print("adaptation events:", sum(a != b for a, b in zip(opts, opts[1:])))


if __name__ == "__main__":
    main()
