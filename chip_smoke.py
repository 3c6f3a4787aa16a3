"""Chip smoke run: the published Swin-T split path on one TPU.

    PYTHONPATH=src python chip_smoke.py

Drives the served path once, in this one process, through the entry points
a user calls, at the published width (Swin-T: 96 wide, depths 2-2-6-2,
800x544 input; random weights from seed 0):

  1. ``build_pipeline`` + ``SplitInferencePipeline.run_trace`` with each of
     the six split options forced once, then a few adaptive frames;
  2. ``CellSimulator.run_stream``: 4 UEs on one ``RanCell`` (python MAC),
     fused head+encode, tails batched at bucket 4; then the same cell with
     the group encode, whose payloads must match byte for byte.

It checks that the Pallas kernels ran (``ops.on_tpu()``, and
``tpu_custom_call`` in the compiled head and tail programs of every
split); that detections agree with a plain f32 reference (``attn_impl=
'xla'``, no codec, matmul precision 'highest') within ``REL_L2_TOL``; that
the fused window kernel agrees with its jnp mirror at every stage within
``KERNEL_TOL``; and that raw payload bytes equal the tracked table.

Lines before the last are smoke readings, not benchmark results.  The last
line is the JSON status.  With no TPU, or when any check fails, it exits
non-zero and prints no status line: nothing here catches an error.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.swin_t_detection import CONFIG
from repro.core.calibration import load_payload_table
from repro.core.cell import CellSimulator, cell_interference_traces
from repro.core.compression import ActivationCodec
from repro.core.pipeline import build_pipeline
from repro.core.ran import RanCell, RanConfig, make_policy
from repro.core.splitting import SERVER_ONLY, UE_ONLY
from repro.data.video import SyntheticVideo, VideoConfig
from repro.kernels import ops
from repro.kernels import window_attention as wa
from repro.models import swin as SW
from repro.runtime.compile_cache import enable_compile_cache

SEED = 0
SPLITS = (1, 2, 3, 4)
ADAPTIVE_LEVELS = (-40.0, -20.0, -10.0, -5.0)   # dB, one adaptive frame each
CELL_UES = 4
CELL_FRAMES = 2
CELL_OPTION = "split2"

# Relative L2 error of the served path's detections against the f32
# reference, per output kind (cls / box / ctr), over every FPN level,
# normalised by the reference's spread: ||out - ref|| / ||ref - mean(ref)||.
# The same comparison on the CPU at this width (seed 0, frame 0):
#   codec only (CPU matmuls are f32):  unsplit 2.7e-6, split1 9.6e-3,
#                                      split2 1.5e-2, split3 2.1e-2,
#                                      split4 2.2e-2
#   with every f32 matmul/conv operand rounded to bf16, the one pass the
#   TPU's default precision takes:     unsplit 2.3e-2, split1 2.6e-2,
#                                      split2 2.7e-2, split3 3.2e-2,
#                                      split4 3.3e-2
# The bound is twice the largest.  Running every shifted block unshifted
# (wrong windows) gives 0.60 on the same measure.
REL_L2_TOL = 6.5e-2
# Fused Pallas window kernel vs its jnp mirror run at 'highest' precision,
# per stage, relative L2.  On the CPU the interpreted kernel equals the
# mirror bit for bit; with the kernel's dot operands rounded to bf16 (one
# MXU pass) the error is 3.0e-3..3.1e-3 at every stage.  Twice that.
KERNEL_TOL = 6.5e-3


def say(key: str, value) -> None:
    print(f"smoke {key}: {value}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"smoke check failed: {what}")


def rel_l2(out, ref) -> float:
    """Max over cls/box/ctr of ||out - ref|| / ||ref - mean(ref)||."""
    errs = []
    for key in ("cls", "box", "ctr"):
        a = np.concatenate([np.asarray(lv[key], np.float64).ravel()
                            for lv in out])
        b = np.concatenate([np.asarray(lv[key], np.float64).ravel()
                            for lv in ref])
        require(np.all(np.isfinite(a)), f"non-finite {key} output")
        errs.append(float(np.linalg.norm(a - b)
                          / np.linalg.norm(b - b.mean())))
    return max(errs)


def reference_forward(cfg):
    """Plain f32 reference: XLA window attention, no codec, matmuls at
    'highest' precision."""
    ref_cfg = dataclasses.replace(cfg, attn_impl="xla")
    fwd = jax.jit(lambda params, img: SW.forward_full(ref_cfg, params, img))

    def run(params, img):
        with jax.default_matmul_precision("highest"):
            return jax.block_until_ready(fwd(params, img))
    return run


def split_outputs(cfg, params, img, codec, split: int):
    """Head -> int8+zlib codec -> tail for one split, through the cached
    head/tail jits the pipeline and the cell use.  Returns (detections,
    compressed payload, decoded server view)."""
    payload = SW.head_apply_jit(cfg, split)(params, img)
    comp = codec.compress(payload)
    view = codec.decompress(comp)
    out = SW.tail_apply_jit(cfg, split)(params, view)
    return jax.block_until_ready(out), comp, view


def frames(cfg, n: int):
    video = SyntheticVideo(VideoConfig(h=cfg.img_h, w=cfg.img_w, seed=SEED))
    return [jnp.asarray(video.frame(t)[0])[None] for t in range(n)]


def timed(fn, reps: int = 3) -> float:
    """Median wall seconds of ``fn()`` (warm; ends in block_until_ready)."""
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def phase_kernel_vs_mirror(cfg):
    """The fused window kernel against its jnp mirror at every stage,
    shifted and not."""
    key = jax.random.PRNGKey(SEED)
    win = cfg.window
    for s in range(cfg.n_stages):
        H, W = cfg.stage_hw(s)
        Hp, Wp = -(-H // win) * win, -(-W // win) * win
        C, nh = cfg.stage_dim(s), cfg.num_heads[s]
        for shift in (0, win // 2):
            k1, k2, key = jax.random.split(key, 3)
            qkv = jax.random.normal(k1, (1, Hp, Wp, 3 * C), jnp.float32)
            bias = jax.random.normal(k2, (nh, win * win, win * win),
                                     jnp.float32)
            mask = jnp.asarray(SW.shift_attn_mask(Hp, Wp, win, shift)
                               if shift else
                               SW.pad_region_mask(Hp, Wp, H, W, win))
            out = ops.fused_window_attention(qkv, bias, mask, window=win,
                                             shift=shift, n_heads=nh)
            bias_p, mask_p = ops._pad_fused_inputs(
                bias, mask, window=win, nwh=Hp // win, nww=Wp // win)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda q, b, m: wa.fused_window_attention_jnp(
                    q, b, m, window=win, shift=shift, n_heads=nh))(
                        qkv, bias_p, mask_p)
            a, b = np.asarray(out, np.float64), np.asarray(ref, np.float64)
            err = float(np.linalg.norm(a - b) / np.linalg.norm(b))
            say(f"kernel_vs_mirror stage{s} shift{shift}",
                f"rel_l2={err:.3e} tol={KERNEL_TOL:.1e}")
            require(err <= KERNEL_TOL, f"fused kernel stage {s} shift "
                    f"{shift}: rel L2 {err} > {KERNEL_TOL}")


def phase_codec_kernels(codec, payload):
    """The codec's encode and decode dispatch to their Pallas kernels,
    at the size of a real split-1 stream."""
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(payload))
    block = codec.quant_block
    flat = jax.ShapeDtypeStruct((-(-n // block) * block,), jnp.float32)
    for delta in (False, True):
        enc = jax.jit(lambda f: ops.codec_encode(f, block=block, delta=delta))
        stream, scales = jax.eval_shape(enc, flat)
        dec = jax.jit(lambda q, s: ops.codec_decode(q, s, block=block,
                                                    delta=delta))
        for name, text in (
                ("encode", enc.lower(flat).compile().as_text()),
                ("decode", dec.lower(stream, scales).compile().as_text())):
            require("tpu_custom_call" in text,
                    f"codec {name} (delta={delta}) is not a Pallas kernel")
    say("codec kernels", f"encode/decode x delta off/on compiled as Pallas "
        f"kernels over a {flat.shape[0] * 4} byte stream")


def phase_splits(cfg, params, img, table):
    """Per split: tpu_custom_call in the compiled head and tail, detections
    against the reference, payload bytes against the table, warm head and
    tail wall time.  Returns compile seconds (first call minus warm call
    of every program run here)."""
    codec = ActivationCodec()
    t0 = time.perf_counter()
    ref_fwd = reference_forward(cfg)
    ref = ref_fwd(params, img)
    cold = time.perf_counter() - t0
    compile_s = cold - timed(lambda: ref_fwd(params, img), reps=1)
    full = SW.forward_full_jit(cfg)
    t0 = time.perf_counter()
    err = rel_l2(full(params, img), ref)
    cold = time.perf_counter() - t0
    compile_s += cold - timed(lambda: full(params, img), reps=1)
    say("unsplit rel_l2", f"{err:.3e} tol={REL_L2_TOL:.1e}")
    require(err <= REL_L2_TOL, f"unsplit forward: rel L2 {err}")
    require("tpu_custom_call" in full.lower(params, img).compile().as_text(),
            "no Pallas kernel in the unsplit forward")
    phase_codec_kernels(codec, SW.head_apply_jit(cfg, 1)(params, img))
    for split in SPLITS:
        opt = f"split{split}"
        t0 = time.perf_counter()
        out, comp, view = split_outputs(cfg, params, img, codec, split)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        split_outputs(cfg, params, img, codec, split)
        warm = time.perf_counter() - t0
        compile_s += cold - warm
        head, tail = SW.head_apply_jit(cfg, split), SW.tail_apply_jit(cfg,
                                                                      split)
        programs = [("head", head, (params, img))]
        if split < cfg.n_stages:       # split4's tail is the FPN head only
            programs.append(("tail", tail, (params, view)))
        for name, jitted, args in programs:
            text = jitted.lower(*args).compile().as_text()
            require("tpu_custom_call" in text,
                    f"no Pallas kernel in the compiled {opt} {name}")
        err = rel_l2(out, ref)
        say(f"{opt} rel_l2", f"{err:.3e} tol={REL_L2_TOL:.1e}")
        require(err <= REL_L2_TOL, f"{opt}: rel L2 {err} > {REL_L2_TOL}")
        row = table[opt]
        say(f"{opt} payload_bytes",
            f"raw={comp.raw_bytes} (table {row['raw']}) "
            f"compressed={comp.compressed_bytes} "
            f"(table {row['compressed']})")
        require(comp.raw_bytes == row["raw"], f"{opt} raw bytes differ")
        head_s = timed(lambda: head(params, img))
        tail_s = timed(lambda: tail(params, view))
        say(f"{opt} wall_s", f"head={head_s:.6f} tail={tail_s:.6f} "
            f"head+codec+tail first_call={cold:.3f} warm={warm:.3f} "
            "(after block_until_ready)")
    return compile_s


def phase_pipeline(cfg, imgs, table):
    """run_trace with each option forced (first call, then warm), then
    adaptive frames.  Returns (pipeline, compile seconds: first call minus
    warm call, summed -- what the head/tail jits above did not cover, such
    as the single-UE pipeline's eager tail ops)."""
    pipe = build_pipeline(cfg=cfg, execute_model=True, seed=SEED)
    require(pipe.plan.options == [UE_ONLY, "split1", "split2", "split3",
                                  "split4", SERVER_ONLY],
            f"unexpected options {pipe.plan.options}")
    compile_s = 0.0
    for opt in pipe.plan.options:
        t0 = time.perf_counter()
        pipe.run_trace(imgs[:1], [-10.0], option=opt)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        (log,) = pipe.run_trace(imgs[:1], [-10.0], option=opt)
        warm = time.perf_counter() - t0
        compile_s += cold - warm
        require(np.isfinite(log.delay_s) and log.option == opt,
                f"run_trace {opt}: {log}")
        require(log.raw_bytes == table[opt]["raw"],
                f"run_trace {opt}: raw bytes {log.raw_bytes}")
        say(f"run_trace {opt}", f"first_call_s={cold:.3f} "
            f"warm_call_s={warm:.3f} compressed={log.compressed_bytes}")
    logs = pipe.run_trace(imgs, ADAPTIVE_LEVELS)
    require(all(np.isfinite(l.delay_s) for l in logs), "adaptive frames")
    say("run_trace adaptive", ", ".join(
        f"{l.interference_db:+.0f}dB->{l.option}" for l in logs))
    return pipe, compile_s


def phase_cell(pipe, imgs, ref):
    """4 UEs through the event engine on one RanCell; every tail batch at
    bucket 4.  Fused head first, then the group encode."""
    trace = cell_interference_traces(CELL_FRAMES, CELL_UES, seed=1)
    runs = {}
    for fused in (True, False):
        cell = CellSimulator(
            plan=pipe.plan, system=pipe.system, codec=ActivationCodec(),
            n_ues=CELL_UES, seed=SEED, execute_model=True, fused_head=fused,
            buckets=(1, 2, CELL_UES), max_wait_s=30.0, engine="python",
            ran=RanCell(policy=make_policy("edf"),
                        cfg=RanConfig(tti_s=0.002)))
        t0 = time.perf_counter()
        res = cell.run_stream(trace, imgs=imgs, option=CELL_OPTION, fps=0.5,
                              keep_outputs=True)
        wall = time.perf_counter() - t0
        logs = res.logs
        require(len(logs) == CELL_FRAMES * CELL_UES
                and not any(l.dropped for l in logs), "cell dropped frames")
        require(all(l.batch_size == CELL_UES for l in logs)
                and res.stats.n_batches == CELL_FRAMES
                and res.stats.occupancy_sum == CELL_FRAMES,
                "cell tails did not run at bucket 4")
        worst = 0.0
        for t, by_ue in enumerate(res.outputs):
            for u, out in by_ue.items():
                worst = max(worst, rel_l2(out, ref[(t + u) % len(imgs)]))
        require(worst <= REL_L2_TOL, f"cell detections: rel L2 {worst}")
        runs[fused] = sorted((l.ue_id, l.frame_idx, l.compressed_bytes)
                             for l in logs)
        say(f"cell fused_head={fused}", f"frames={len(logs)} "
            f"batches={res.stats.n_batches} worst_rel_l2={worst:.3e} "
            f"wall_s={wall:.3f}")
    require(runs[True] == runs[False],
            "fused-head and group-encode payload bytes differ")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    require(ops.on_tpu(), "ops.on_tpu() is false on a TPU backend")
    say("device_kind", dev.device_kind)
    say("compile_cache", cache_dir)
    cfg = CONFIG
    require((cfg.embed_dim, cfg.depths, cfg.img_w, cfg.img_h)
            == (96, (2, 2, 6, 2), 800, 544), "not the published Swin-T")
    t_start = time.perf_counter()
    params = SW.init(cfg, jax.random.PRNGKey(SEED))
    imgs = frames(cfg, len(ADAPTIVE_LEVELS))
    table = load_payload_table()

    phase_kernel_vs_mirror(cfg)
    compile_s = phase_splits(cfg, params, imgs[0], table)
    pipe, pipe_compile_s = phase_pipeline(cfg, imgs, table)
    say("compile_s", f"{compile_s + pipe_compile_s:.3f} (first call minus "
        f"warm call: head/codec/tail/reference jits {compile_s:.3f}, "
        f"run_trace {pipe_compile_s:.3f})")
    ref_fwd = reference_forward(cfg)
    phase_cell(pipe, imgs, [ref_fwd(pipe.plan.params, im) for im in imgs])
    say("total_s", f"{time.perf_counter() - t_start:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
