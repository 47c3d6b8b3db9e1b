"""Benchmark harness: one module per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV at the end; detailed JSON lands in
results/.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="single seed (CI-speed)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import common as C
    seeds = (0,) if args.quick else C.SEEDS

    rows = []  # (name, us_per_call, derived)

    def section(title):
        print(f"\n===== {title} =====", flush=True)

    section("Fig 2 — cost-quality AUC, Eagle vs KNN/MLP/SVM")
    from benchmarks import fig2_auc
    t0 = time.perf_counter()
    f2 = fig2_auc.run(seeds=seeds)
    us = (time.perf_counter() - t0) * 1e6
    imp = f2["regimes"]["online"]["improvement_vs"]
    rows.append(("fig2_auc_online", us,
                 f"eagle_vs_knn=+{imp['knn']:.2f}%"
                 f"|mlp=+{imp['mlp']:.2f}%|svm=+{imp['svm']:.2f}%"))

    section("Table 3a — init/incremental update timing")
    from benchmarks import table3a_timing
    t0 = time.perf_counter()
    t3 = table3a_timing.run(seeds=seeds)
    us = (time.perf_counter() - t0) * 1e6
    r = t3["eagle_pct_of_baseline_mean"]
    rows.append(("table3a_timing", us,
                 f"eagle_pct_of_baselines:70%={r['70%']:.2f}"
                 f"|85%={r['85%']:.2f}|100%={r['100%']:.2f}"))

    section("Fig 3b — online adaptation quality")
    from benchmarks import fig3b_incremental
    t0 = time.perf_counter()
    f3 = fig3b_incremental.run(seeds=seeds)
    us = (time.perf_counter() - t0) * 1e6
    i3 = f3["eagle_improvement_vs_baseline_mean_pct"]
    rows.append(("fig3b_incremental", us,
                 f"eagle_vs_mean:+{i3['70%']:.2f}%/+{i3['85%']:.2f}%"
                 f"/+{i3['100%']:.2f}%"))

    section("Fig 4 — ablations (components, N sweep)")
    from benchmarks import fig4_ablation
    t0 = time.perf_counter()
    f4 = fig4_ablation.run(seeds=seeds)
    us = (time.perf_counter() - t0) * 1e6
    c = f4["components"]
    rows.append(("fig4_ablation", us,
                 f"eagle={c['eagle']['mean']:.3f}"
                 f"|global={c['global_only']['mean']:.3f}"
                 f"|local={c['local_only']['mean']:.3f}"))

    section("Batch routing latency — fused route_batch vs legacy path")
    from benchmarks import route_batch_bench
    for n, us, d in route_batch_bench.run(smoke=args.quick):
        rows.append((n, us, d))

    section("Admission queue — coalescing, backpressure, goodput")
    from benchmarks import queue_bench
    for n, us, d in queue_bench.run(smoke=args.quick):
        rows.append((n, us, d))

    section("Kernel microbenchmarks")
    from benchmarks import kernels_bench
    for n, us, d in kernels_bench.run():
        rows.append((n, us, d))

    section("Roofline (from dry-run sweep)")
    from benchmarks import roofline
    rl = roofline.run(verbose=not args.quick)
    ok = [r for r in rl if r["mesh"] == "single"]
    if ok:
        n_fit = sum(r["fits_hbm"] for r in ok)
        rows.append(("roofline_single_pod", 0.0,
                     f"combos={len(ok)}|fits_hbm={n_fit}"
                     f"|median_useful={np.median([r['useful_flops_fraction'] for r in ok]):.3f}"))
        picks = roofline.pick_hillclimb(rl)
        for k, v in picks.items():
            print(f"  hillclimb[{k}]: {v['arch']} x {v['shape']} "
                  f"(dominant {v['dominant']})")

    print("\nname,us_per_call,derived")
    for n, us, d in rows:
        print(f"{n},{us:.1f},{d}")


if __name__ == "__main__":
    main()
