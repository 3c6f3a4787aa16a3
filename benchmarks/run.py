"""Benchmark harness: one module per paper figure + the roofline table.

    PYTHONPATH=src python -m benchmarks.run [--only substr]

Prints one ``name,us_per_call,derived`` CSV line per bench (collected at
the end) and writes detailed rows to results/*.json.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true",
                    help="smoke mode (CI): the registry below already runs "
                         "every bench in its reduced/fast variant; this flag "
                         "exists so automation can state the intent "
                         "explicitly and future slow registrations must "
                         "respect it")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_adaptive, bench_cell, bench_chaos,
                            bench_chaos_corr, bench_compression, bench_dupf,
                            bench_e2e_delay, bench_energy_breakdown,
                            bench_energy_privacy, bench_estimator,
                            bench_kernel_cost, bench_mobility, bench_ran,
                            bench_scale, bench_streaming, bench_tx_energy)

    benches = [
        # fast mode: reduced model, same legacy-vs-fused comparison + the
        # bit-identity assert (the full-size run is the module's __main__)
        ("fig3_compression", lambda: bench_compression.run(fast=True)),
        ("fig4_e2e_delay", bench_e2e_delay.run),
        ("fig5_energy_privacy", bench_energy_privacy.run),
        ("fig6_tx_energy", bench_tx_energy.run),
        ("fig7_energy_breakdown", bench_energy_breakdown.run),
        ("fig8_dupf", bench_dupf.run),
        ("estimator_ablation", bench_estimator.run),
        ("adaptive_vs_fixed", bench_adaptive.run),
        ("cell_batching", bench_cell.run),
        # fast mode: smaller load sweep + coarser TTI, same acceptance
        # anchors (idle-cell calibration, load degradation, EDF vs RR)
        ("ran_scheduler", lambda: bench_ran.run(fast=True)),
        # fast mode: shorter trace + coarser fps sweep, same acceptance
        # anchors (miss/drop strictly rise with load, lock-step flat)
        ("streaming_backlog", lambda: bench_streaming.run(fast=True)),
        # fast mode: shorter trace + coarser speed sweep, same acceptance
        # anchors (static point bitwise == today's engine, miss/age rise
        # with speed, dUPF beats cUPF mean+std under identical seeds)
        ("mobility_handover", lambda: bench_mobility.run(fast=True)),
        # fast mode: ~1k flows + 2 forced devices, same acceptance
        # anchors (oracle schedule identical, speedup floor, sub-linear
        # device scaling); the full 64 -> 50k sweep is the module's
        # __main__ and commits results/bench_scale.json
        ("city_scale", lambda: bench_scale.run(fast=True)),
        # fast mode: shorter trace + coarser severity sweep, same
        # acceptance anchors (inert chaos bitwise == today's engine,
        # recovery cost rises with outage duration, failover beats
        # no-failover); writes bench_chaos_fast.json so the CI smoke
        # never clobbers the committed full-run curves
        ("chaos_recovery", lambda: bench_chaos.run(fast=True)),
        # fast mode: 1k-flow drain instead of 10k, same acceptance
        # anchors (correlated site faults strictly worse than staggered
        # faults of equal marginal rate, vectorized engine field-exact
        # on the correlated run -- the CI vectorized-chaos smoke --
        # batched park/adopt drain <= 1.5x chaos-free); writes
        # bench_chaos_corr_fast.json, never the committed full curves
        ("chaos_correlated", lambda: bench_chaos_corr.run(fast=True)),
        # compiles the reduced Swin forward and pushes it through the
        # loop-aware HLO analyzer (launch/hlo_cost.py) + roofline table
        # (benchmarks/roofline.py) -- the dry-run-free path, so the CI
        # smoke exercises both formerly write-only modules and commits
        # results/bench_kernel_cost.json
        ("kernel_cost", lambda: bench_kernel_cost.run(fast=True)),
        # fused Swin head (one device call for head + int8 quant epilogue,
        # DESIGN.md §13) vs the eager-XLA + separate-quant baseline:
        # asserts payload byte-identity and the 2x speedup floor; the
        # all-splits full run is the module's __main__ and commits
        # results/bench_head_fused.json
        ("head_fused", lambda: bench_kernel_cost.run_head_fused(fast=True)),
    ]
    if args.only:
        benches = [(n, f) for n, f in benches if args.only in n]

    lines = []
    failed = 0
    for name, fn in benches:
        print(f"== {name} ==", flush=True)
        t0 = time.perf_counter()
        try:
            line = fn()
            dt = time.perf_counter() - t0
            print(f"   ({dt:.1f}s)\n")
            lines.append(line)
        except Exception:
            failed += 1
            traceback.print_exc()
            lines.append(f"{name},0,FAILED")

    # roofline summary (reads the dry-run artifact if present)
    try:
        import os
        from benchmarks.roofline import load, table
        art = ("results/dryrun_optimized.json"
               if os.path.exists("results/dryrun_optimized.json")
               else "results/dryrun_baseline.json")
        cells = load(art)
        rows = [r for r in table(cells) if r["status"] == "OK"]
        worst = min(rows, key=lambda r: r["roofline_frac"])
        best = max(rows, key=lambda r: r["roofline_frac"])
        lines.append(f"roofline,0,cells={len(rows)};best={best['arch']}/"
                     f"{best['shape']}={100*best['roofline_frac']:.1f}%;"
                     f"worst={worst['arch']}/{worst['shape']}="
                     f"{100*worst['roofline_frac']:.2f}%")
    except Exception:
        lines.append("roofline,0,missing_dryrun_artifact")

    print("name,us_per_call,derived")
    for l in lines:
        print(l)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
