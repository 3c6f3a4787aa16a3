"""Readings that set a cell's limits: the program on many seeds, and the
control, in one process on the chip.

    python3 chipbench/control.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--fault-seeds 1,2,3] [--out file.json]

For each seed it drives the cell's timed path
(``Cell.play``, the rounds a run plays) until the run's sample of frames
is full, and compares the sample with the reference exactly as a run
does: that is the program's reading.  For each control seed it puts the
reference, computed in bfloat16 (the precision below the configuration's
float32), in the program's place on the same sampled frames, and compares
it the same way: the control's reading.  For each fault seed it plants
each fault the cell can have (``chipbench/lib/faults.py``) under the
timed path and reads it the same way, at the cell's own size.  Beside
the number ``correct`` compares (``cls``) it reports the box and
centerness maps' gaps.  The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench.lib import faults, harness, model  # noqa: E402


def readings(items, refs):
    """Worst gap over the sampled frames, per output map."""
    return {k: max(harness.rel_l2(o, refs(i), k) for i, o in items)
            for k in ("cls", "box", "ctr")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    spec = harness.load_cell(a.workload)
    harness.require_chips(spec.chips)
    harness.enable_compile_cache()
    t = spec.traffic
    shape = model.Shape.from_config(spec.config)
    split, block = t.split, spec.config["codec"]["quant_block"]
    f32 = jax.jit(lambda p, x: model.reference_forward(shape, p, x, split,
                                                       block))
    bf16 = jax.jit(lambda p, x: model.reference_forward(
        shape, p, x, split, block, dtype=jnp.bfloat16))
    pool = t.pool(shape.img_h, shape.img_w)
    rounds = math.ceil(harness.SAMPLE_FRAMES / t.n_ues)   # fills the sample
    control_seeds = {int(s) for s in a.control_seeds.split(",") if s}
    params = model.init_params(shape, spec.config["weights_seed"])
    cache = {}

    def refs(i):
        if i not in cache:
            cache[i] = jax.device_get(f32(params, jnp.asarray(pool[i])))
        return cache[i]

    def sample_of(seed):
        """The sample a run of ``seed`` compares, and whether every round
        ran as full batches."""
        cell = harness.Cell(spec, params, pool, seed)
        cell.play(0)
        sample = harness.Sample(harness.SAMPLE_FRAMES, seed)
        ok = True
        for r in range(1, rounds + 1):
            rd = cell.play(r)
            ok &= rd.ok
            for u, out in rd.outs.items():
                sample.offer(rd.frames[u], out)
        return [(i, jax.device_get(o)) for i, o in sample.items], ok

    rows = []
    for seed in [int(s) for s in a.seeds.split(",") if s]:
        t0 = time.perf_counter()
        items, ok = sample_of(seed)
        row = {"seed": seed, "rounds_ok": bool(ok), "frames": len(items),
               "program": readings(items, refs)}
        if seed in control_seeds:
            ctrl = [(i, jax.device_get(bf16(params, jnp.asarray(pool[i]))))
                    for i, _ in items]
            row["control_bf16"] = readings(ctrl, refs)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
    for seed in [int(s) for s in a.fault_seeds.split(",") if s]:
        for name in faults.applicable(t):
            with faults.planted(name):
                items, ok = sample_of(seed)
            row = {"seed": seed, "fault": name, "rounds_ok": bool(ok),
                   "frames": len(items), "reading": readings(items, refs)}
            print(json.dumps(row), flush=True)
            rows.append(row)
    if a.out:
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "device":
                       jax.devices()[0].device_kind, "rows": rows}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
