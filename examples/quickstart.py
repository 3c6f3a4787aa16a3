"""Quickstart: split a Swin detector, compress the boundary, pick a split
adaptively.  Runs in ~1 min on CPU.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.swin_t_detection import reduced
from repro.core import (ActivationCodec, SwinSplitPlan, UE_ONLY, SERVER_ONLY,
                        calibrate)
from repro.core.adaptive import AdaptiveController, Objective
from repro.core.channel import dupf_path, iq_spectrogram, observe_kpms
from repro.core.throughput import train_estimator
from repro.data.video import SyntheticVideo, VideoConfig
from repro.models import swin as SW
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # 1. an unmodified Swin-T detector (reduced size for CPU)
    cfg = reduced()
    params = SW.init(cfg, jax.random.PRNGKey(0))
    video = SyntheticVideo(VideoConfig(h=cfg.img_h, w=cfg.img_w))
    img = jnp.asarray(video.frame(0)[0])[None]

    # 2. partition its forward pass at stage boundaries -- no retraining
    plan = SwinSplitPlan(cfg, params)
    full = SW.forward_full(cfg, params, img)
    payload, _ = plan.head(img, "split2")          # UE side
    print(f"split2 boundary: {len(jax.tree.leaves(payload))} tensors, "
          f"{sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(payload)) / 1e6:.2f} MB raw")

    # 3. compress: Pallas INT8 quant + zlib (the paper's pipeline)
    codec = ActivationCodec()
    comp = codec.compress(payload)
    print(f"compressed: {comp.compressed_bytes / 1e6:.2f} MB "
          f"({100 * (1 - comp.ratio):.1f}% reduction)")

    # 4. server side completes detection from the decompressed payload
    out = plan.tail(codec.decompress(comp), "split2")
    drift = np.abs(np.asarray(out[0]["cls"]) - np.asarray(full[0]["cls"])).mean()
    print(f"detection logit drift through codec: {drift:.4f} (accuracy preserved)")

    # 5. the AF picks the split from live radio observations
    system = calibrate()                           # calibrated to paper §V
    est = train_estimator(system.channel, "kpm+spec", n_train=800, steps=150)
    ctrl = AdaptiveController(
        system=system, estimator=est,
        objective=Objective(w_delay=1.0, w_energy=0.2, w_privacy=0.1),
        path=dupf_path(),
        privacy_profile={UE_ONLY: 0.0, SERVER_ONLY: 1.0, "split1": 0.53,
                         "split2": 0.42, "split3": 0.33, "split4": 0.27})
    rng = np.random.default_rng(0)
    for lvl in (-40, -20, -5):
        ctrl.interference_db = lvl
        d = ctrl.decide(observe_kpms(lvl, False, rng),
                        iq_spectrogram(lvl, False, rng),
                        plan.options)
        print(f"interference {lvl:+d} dB -> {d.option:12s} "
              f"(predicted delay {d.delay_s * 1e3:6.0f} ms, "
              f"energy {d.energy_j:5.1f} J, privacy {d.privacy:.2f})")


if __name__ == "__main__":
    main()
